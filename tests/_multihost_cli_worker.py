"""Worker for the multi-process CLI test: runs the real
``tpu_als.cli train`` entry under a 2-process gloo deployment (CPU
devices forced before first JAX use, in a wrapper, whatever the shell
exports)."""

import os
import sys

os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax

jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_cpu_collectives_implementation", "gloo")

if __name__ == "__main__":
    # REAL two-process rendezvous for every mode.  The cli mode's
    # cmd_train calls init_distributed itself, but the fit* modes drive
    # ALS.fit directly — without this they would silently run as two
    # INDEPENDENT single-process fits (jax.process_count() == 1), and the
    # parent's comparisons would still pass because the single- and
    # multi-process math agree: exactly the failure mode that hid this
    # for a round.  The assertion pins the rendezvous.
    from tpu_als.parallel.multihost import init_distributed

    _, _pcount = init_distributed()
    assert _pcount == 2, f"expected a 2-process rendezvous, got {_pcount}"
    if os.environ.get("MH_MODE") == "fit_ckpt_sharded":
        # shard-per-process checkpointing: no cross-host factor gather on
        # the checkpoint path; a resume from the sharded directory must
        # reproduce the uninterrupted run
        import numpy as np

        from tpu_als import ALS
        from tpu_als.io.movielens import synthetic_movielens
        from tpu_als.parallel.mesh import make_mesh

        frame = synthetic_movielens(80, 30, 1500, seed=2)
        ckdir = os.environ["MH_OUT"] + ".ckpt"
        ALS(rank=3, maxIter=2, regParam=0.02, seed=0, mesh=make_mesh(),
            checkpointDir=ckdir, checkpointInterval=2,
            checkpointSharded=True).fit(frame)
        ckpt = os.path.join(ckdir, "als_checkpoint")
        import json

        with open(os.path.join(ckpt, "manifest.json")) as f:
            assert json.load(f)["sharded"] is True
        resumed = ALS(rank=3, maxIter=4, regParam=0.02, seed=0,
                      mesh=make_mesh(), resumeFrom=ckpt).fit(frame)
        straight = ALS(rank=3, maxIter=4, regParam=0.02, seed=0,
                       mesh=make_mesh()).fit(frame)
        if jax.process_index() == 0:
            np.savez(os.environ["MH_OUT"] + ".ckpt.npz",
                     Ur=resumed._U, Vr=resumed._V,
                     Us=straight._U, Vs=straight._V)
        print("sharded ckpt worker done", flush=True)
    elif os.environ.get("MH_MODE") == "fit_ckpt":
        # multi-process checkpoint -> resume == uninterrupted run
        import numpy as np

        from tpu_als import ALS
        from tpu_als.io.movielens import synthetic_movielens
        from tpu_als.parallel.mesh import make_mesh

        frame = synthetic_movielens(80, 30, 1500, seed=2)
        ckdir = os.environ["MH_OUT"] + ".ckpt"
        ALS(rank=3, maxIter=2, regParam=0.02, seed=0, mesh=make_mesh(),
            checkpointDir=ckdir, checkpointInterval=2).fit(frame)
        resumed = ALS(rank=3, maxIter=4, regParam=0.02, seed=0,
                      mesh=make_mesh(),
                      resumeFrom=os.path.join(ckdir, "als_checkpoint"),
                      ).fit(frame)
        straight = ALS(rank=3, maxIter=4, regParam=0.02, seed=0,
                       mesh=make_mesh()).fit(frame)
        if jax.process_index() == 0:
            np.savez(os.environ["MH_OUT"] + ".ckpt.npz",
                     Ur=resumed._U, Vr=resumed._V,
                     Us=straight._U, Vs=straight._V)
        print("ckpt worker done", flush=True)
    elif os.environ.get("MH_MODE") == "cli_perhost":
        # the CLI per-host surface end-to-end: each process writes its
        # own csv split, the SAME command with --per-host-data and a
        # {proc} placeholder loads them, trains, and process 0 saves
        import numpy as np

        from tpu_als.cli import main
        from tpu_als.io.movielens import synthetic_movielens

        pid = jax.process_index()
        full = synthetic_movielens(90, 35, 2000, seed=4)
        sel = np.arange(len(full)) % 2 == pid
        base = os.environ["MH_OUT"]
        np.savetxt(
            base + f".part{pid}.csv",
            np.column_stack([
                np.asarray(full["user"])[sel],
                np.asarray(full["item"])[sel],
                np.asarray(full["rating"])[sel],
                np.zeros(int(sel.sum()), np.int64),
            ]),
            delimiter=",", header="userId,movieId,rating,timestamp",
            comments="", fmt=["%d", "%d", "%.6f", "%d"])
        main(["train", "--data", "csv:" + base + ".part{proc}.csv",
              "--per-host-data", "--devices", "0", "--rank", "4",
              "--max-iter", "3", "--reg-param", "0.02", "--seed", "0",
              "--output", base + ".model"])
        print("cli perhost worker done", flush=True)
    elif os.environ.get("MH_MODE") == "cli_stream":
        # the config-3 CLI one-liner: ONE shared string-id csv, each
        # process streams only its byte range (--per-host-data with a
        # stream: spec needs no {proc} file splits), ids agreed
        # collectively, process 0 saves the model + label sidecar
        from tpu_als.cli import main

        base = os.environ["MH_OUT"]
        main(["train", "--data", "stream:" + os.environ["MH_CSV"],
              "--per-host-data", "--devices", "0", "--rank", "4",
              "--max-iter", "3", "--reg-param", "0.02", "--seed", "0",
              "--output", base + ".model"])
        print("cli stream worker done", flush=True)
    elif os.environ.get("MH_MODE") == "gate_diverge":
        # processes deliberately disagree on a fit knob: the config gate
        # (fit's FIRST collective) must turn what would be a distributed
        # hang into a ValueError on EVERY process
        from tpu_als import ALS
        from tpu_als.io.movielens import synthetic_movielens
        from tpu_als.parallel.mesh import make_mesh

        pid = jax.process_index()
        frame = synthetic_movielens(60, 30, 800, seed=3)
        try:
            ALS(rank=3, maxIter=2, seed=0, mesh=make_mesh(),
                fitCallbackInterval=1 + pid,  # the divergence
                fitCallback=lambda it, U, V: None).fit(frame)
        except ValueError as e:
            assert "disagree" in str(e), e
            print("gate worker caught divergence", flush=True)
        else:
            raise AssertionError("divergent fit config was not rejected")
    elif os.environ.get("MH_MODE") == "nan_ratings":
        # ONE host's data contains a nan rating: the collective finite
        # check must raise on EVERY host (a one-sided abort would
        # strand the peer in the next collective — code-review r4)
        import numpy as np

        from tpu_als import ALS
        from tpu_als.io.movielens import synthetic_movielens
        from tpu_als.parallel.mesh import make_mesh

        pid = jax.process_index()
        frame = synthetic_movielens(60, 30, 800, seed=3)
        if pid == 1:
            r = np.asarray(frame["rating"]).copy()
            r[5] = np.nan
            from tpu_als.utils.frame import ColumnarFrame

            frame = ColumnarFrame({"user": np.asarray(frame["user"]),
                                   "item": np.asarray(frame["item"]),
                                   "rating": r})
        try:
            ALS(rank=3, maxIter=2, seed=0, mesh=make_mesh()).fit(frame)
        except ValueError as e:
            assert "non-finite" in str(e), e
            print("nan worker caught bad ratings", flush=True)
        else:
            raise AssertionError("nan ratings were not rejected")
    elif os.environ.get("MH_MODE") == "gate_diverge_strategy":
        # divergence in gatherStrategy specifically: the knob that decides
        # WHICH collectives the compiled step issues (ring pairs ppermute
        # against all_gather = hang).  No callback/checkpoint knobs set,
        # so only the strategy/cg fields of the gate can catch it
        # (advisor r3, medium).
        from tpu_als import ALS
        from tpu_als.io.movielens import synthetic_movielens
        from tpu_als.parallel.mesh import make_mesh

        pid = jax.process_index()
        frame = synthetic_movielens(60, 30, 800, seed=3)
        try:
            ALS(rank=3, maxIter=2, seed=0, mesh=make_mesh(),
                gatherStrategy="ring" if pid else "all_gather",
                ).fit(frame)
        except ValueError as e:
            assert "gatherStrategy" in str(e), e
            print("gate worker caught divergence", flush=True)
        else:
            raise AssertionError("divergent gatherStrategy not rejected")
    elif os.environ.get("MH_MODE") == "fit_perhost":
        # per-host disjoint files: each process writes + loads ONLY its
        # half of the dataset (row parity split), fits with
        # dataMode='per_host', and the factors must match the
        # single-process fit of the full data.  fitCallback runs too —
        # multi-process callbacks gather collectively, observe on proc 0.
        import numpy as np

        from tpu_als import ALS
        from tpu_als.io.movielens import (
            load_movielens_csv,
            synthetic_movielens,
        )
        from tpu_als.parallel.mesh import make_mesh

        pid = jax.process_index()
        full = synthetic_movielens(100, 40, 2500, seed=1)
        sel = np.arange(len(full)) % 2 == pid
        part_path = os.environ["MH_OUT"] + f".part{pid}.csv"
        np.savetxt(
            part_path,
            np.column_stack([
                np.asarray(full["user"])[sel],
                np.asarray(full["item"])[sel],
                np.asarray(full["rating"])[sel],
                np.zeros(int(sel.sum()), np.int64),
            ]),
            delimiter=",", header="userId,movieId,rating,timestamp",
            comments="", fmt=["%d", "%d", "%.6f", "%d"])
        mine = load_movielens_csv(part_path)
        seen = []
        model = ALS(rank=4, maxIter=3, regParam=0.02, seed=0,
                    mesh=make_mesh(), dataMode="per_host",
                    fitCallback=lambda it, U, V: seen.append(it)).fit(mine)
        if pid == 0:
            assert seen == [1, 2, 3], seen  # gathered + invoked every iter
            np.savez(os.environ["MH_OUT"] + ".fit.npz",
                     U=model._U, V=model._V,
                     uids=model._user_map.ids, iids=model._item_map.ids)
        else:
            assert seen == [], seen  # peers gather but never observe
        print("perhost worker done", flush=True)
    elif os.environ.get("MH_MODE", "").startswith("fit"):
        # multi-process ALS.fit: every host fits the same replicated frame
        import numpy as np

        from tpu_als import ALS
        from tpu_als.io.movielens import synthetic_movielens
        from tpu_als.parallel.mesh import make_mesh

        strategy = {"fit": "all_gather", "fit_ring": "ring",
                    "fit_a2a": "all_to_all"}[os.environ["MH_MODE"]]
        if strategy == "all_to_all":
            # banded-sparse layout: each user rates a private 4-item
            # block, so the exchange plan is NON-degenerate at D=4
            # (a dense frame would silently fall back to all_gather and
            # test nothing)
            from tpu_als.utils.frame import ColumnarFrame

            uu = np.repeat(np.arange(32), 4)
            ii = (np.arange(128) * 2) % 256
            rr = (1.0 + (np.arange(128) % 4)).astype(np.float32)
            frame = ColumnarFrame({"user": uu, "item": ii, "rating": rr})
        else:
            frame = synthetic_movielens(100, 40, 2500, seed=1)
        model = ALS(rank=4, maxIter=3, regParam=0.02, seed=0,
                    mesh=make_mesh(), gatherStrategy=strategy).fit(frame)
        if jax.process_index() == 0:
            np.savez(os.environ["MH_OUT"] + ".fit.npz",
                     U=model._U, V=model._V,
                     uids=model._user_map.ids, iids=model._item_map.ids)
        print("fit worker done", flush=True)
    else:
        from tpu_als.cli import main

        main(["train", "--data", "synthetic:120x50x3000", "--rank", "4",
              "--max-iter", "3", "--reg-param", "0.01", "--seed", "0",
              "--devices", "0", "--output", os.environ["MH_OUT"]])
        print("cli worker done", flush=True)
