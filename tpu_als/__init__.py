"""tpu_als — a TPU-native recommender framework.

Reimplements the full capability surface of the reference repo
(``amy-leaf/Recommender-System-using-Apache-Spark-MLlib-``, a Spark MLlib ALS
recommender — see SURVEY.md; the reference mount was empty, so the spec is the
``pyspark.ml.recommendation.ALS`` stack it delegates to) as an idiomatic
JAX/XLA stack:

- factor matrices are sharded ``jax.Array``s on a named device mesh,
- each ALS half-step is one batched normal-equation build + Cholesky solve,
- the Spark shuffle is replaced by on-device collectives
  (``all_gather`` / ring ``ppermute``),
- new ratings fold in via a jitted incremental update instead of a refit.

Package map (SURVEY.md §7):
  ops/       batched numerics: normal equations, Cholesky/NNLS solves, top-k
  core/      ratings containers (bucketed padded CSR), ALS loop, fold-in
  parallel/  mesh helpers + gather strategies (replicate/all_gather/ring)
  api/       Param system, ALS Estimator / ALSModel, evaluators, tuning
  io/        MovieLens loaders, checkpoint/persistence
  stream/    micro-batch fold-in driver
  live/      rating events -> fold-in -> incremental publish, under serving
  models/    two-tower retrieval model warm-started from ALS factors
"""

__version__ = "0.1.0"

from tpu_als.api.estimator import ALS, ALSModel  # noqa: F401
from tpu_als.api.pipeline import (  # noqa: F401
    IndexToString,
    Pipeline,
    PipelineModel,
    StringIndexer,
    StringIndexerModel,
)
from tpu_als.api.evaluation import (  # noqa: F401
    RankingEvaluator,
    RankingMetrics,
    RegressionMetrics,
    RegressionEvaluator,
)
from tpu_als.api.tuning import (  # noqa: F401
    CrossValidator,
    CrossValidatorModel,
    ParamGridBuilder,
    TrainValidationSplit,
    TrainValidationSplitModel,
)
from tpu_als.core.ratings import IdMap  # noqa: F401
from tpu_als.live import LiveUpdater  # noqa: F401
from tpu_als.parallel.mesh import make_mesh  # noqa: F401
from tpu_als.stream.microbatch import FoldInServer  # noqa: F401
from tpu_als.utils.frame import ColumnarFrame  # noqa: F401
