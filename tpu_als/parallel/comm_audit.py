"""Collective-traffic audit: count the bytes the *traced computation*
actually moves, straight from the jaxpr.

``trainer.comm_bytes_per_iter`` is a closed-form model (container-derived
arithmetic).  This module derives the same per-device quantity from the
step function's jaxpr — every ``all_gather`` / ``ppermute`` / ``psum`` /
``all_to_all`` equation, scaled by the trip counts of enclosing ``scan``s
— so a divergence between what the step *compiles* and what the model
*claims* fails a test instead of silently mis-reporting the CLI traffic
line (VERDICT r3 weak #7: the model was only ever checked against its own
inputs).  The jaxpr is what XLA lowers, so this is the strongest
validation available without an on-chip profiler trace; the byte
conventions per primitive mirror the model's documented ones
(trainer.comm_bytes_per_iter docstring):

- ``all_gather``  → received bytes, ``(S−1)/S × |out|``
- ``ppermute``    → received bytes, ``|out|`` per rotation
- ``psum``        → bidirectional-ring all-reduce, ``2·(S−1)/S × |out|``
- ``all_to_all``  → sent + received minus the self slice,
  ``2·(S−1)/S × |out|``
- ``cond``        → one branch executes per call: branches moving equal
  totals count once; disagreeing branches raise (data-dependent traffic)
- ``while``       → a collective in the body OR the predicate raises
  (unbounded trip count cannot be scaled)

:func:`remote_dma_bytes` extends the audit to traffic NO collective
primitive represents: the fused-comm ring kernel
(``solve_backend='gather_fused_ring'``) moves its inter-chip bytes with
``make_async_remote_copy`` *inside* a ``pallas_call``, visible only as
``dma_start`` equations in the kernel jaxpr.  The ``comm_audit`` contract
(analysis/contracts.py) pins both counters to
``trainer.comm_bytes_per_iter``'s closed forms.
"""

from __future__ import annotations

import numpy as np

import jax


def _aval_bytes(aval):
    return int(np.prod(aval.shape)) * np.dtype(aval.dtype).itemsize


def _out_bytes(eqn):
    return sum(_aval_bytes(v.aval) for v in eqn.outvars
               if getattr(v, "aval", None) is not None)


def collective_bytes(fn, *args, axis_size):
    """Per-device collective bytes of one call of ``fn(*args)``.

    ``axis_size``: size of the (single) mesh axis the collectives run
    over — needed because psum/all_gather byte formulas depend on it and
    the jaxpr does not carry the mesh.

    Returns ``(total_bytes, breakdown)`` where breakdown maps primitive
    name -> bytes.  Raises on a collective inside a ``while`` whose trip
    count the jaxpr cannot bound (none exist in this codebase: the tile
    loops are static-bound ``fori_loop``s, which lower to ``scan``).
    """
    closed = jax.make_jaxpr(fn)(*args)
    breakdown = {}
    # one name set for both the byte counter and the while-loop guard —
    # a primitive recognized by one but not the other would let a
    # collective hide inside a while body uncounted
    COLLECTIVES = ("all_gather", "ppermute", "psum", "psum2",
                   "psum_invariant", "all_to_all")

    S = int(axis_size)

    def walk(jaxpr, mult, out):
        def add(name, nbytes):
            out[name] = out.get(name, 0) + int(nbytes)

        for eqn in jaxpr.eqns:
            name = eqn.primitive.name
            if name == "all_gather":
                gsize = int(eqn.params.get("axis_size", S))
                add(name, mult * (gsize - 1) / gsize * _out_bytes(eqn))
            elif name == "ppermute":
                add(name, mult * _out_bytes(eqn))
            elif name in ("psum", "psum2", "psum_invariant"):
                add("psum", mult * 2 * (S - 1) / S * _out_bytes(eqn))
            elif name == "all_to_all":
                add(name, mult * 2 * (S - 1) / S * _out_bytes(eqn))
            elif name == "scan":
                walk(eqn.params["jaxpr"].jaxpr,
                     mult * int(eqn.params["length"]), out)
            elif name == "while":
                # both sub-jaxprs run an unbounded number of times —
                # a collective in EITHER (a converged-everywhere psum
                # predicate is the classic case) is unscalable here
                if (_has_collective(eqn.params["body_jaxpr"].jaxpr)
                        or _has_collective(eqn.params["cond_jaxpr"].jaxpr)):
                    raise ValueError(
                        "collective inside a while loop with unbounded "
                        "trip count — the audit cannot scale it; use a "
                        "static-bound fori_loop/scan")
            elif name == "cond":
                # exactly one branch executes per call: counting all
                # branches would over-report.  Branches that move the
                # same total are counted once; disagreeing branches make
                # the per-iteration traffic data-dependent, which the
                # closed-form model cannot represent — raise.
                per_branch = []
                for br in eqn.params["branches"]:
                    sub = {}
                    walk(br.jaxpr, mult, sub)
                    per_branch.append(sub)
                # full per-primitive dicts, not grand totals: branches
                # moving the same bytes through DIFFERENT primitives
                # would make the breakdown's attribution data-dependent
                if any(d != per_branch[0] for d in per_branch[1:]):
                    raise ValueError(
                        "cond branches move different collective "
                        f"traffic {per_branch} — per-iteration traffic "
                        "is data-dependent and unauditable")
                for k, v in per_branch[0].items():
                    add(k, v)
            else:
                for p in ("jaxpr", "call_jaxpr"):
                    inner = eqn.params.get(p) if eqn.params else None
                    if inner is not None:
                        walk(getattr(inner, "jaxpr", inner), mult, out)

    def _has_collective(jaxpr):
        found = []

        def probe(jp):
            for eqn in jp.eqns:
                if eqn.primitive.name in COLLECTIVES:
                    found.append(eqn.primitive.name)
                for p in ("jaxpr", "call_jaxpr", "body_jaxpr",
                          "cond_jaxpr"):
                    inner = eqn.params.get(p) if eqn.params else None
                    if inner is not None:
                        probe(getattr(inner, "jaxpr", inner))
                for br in (eqn.params.get("branches", ())
                           if eqn.params else ()):
                    probe(getattr(br, "jaxpr", br))
        probe(jaxpr)
        return bool(found)

    walk(closed.jaxpr, 1, breakdown)
    # the jaxpr is per-program; under shard_map the collectives are
    # per-device ops already, so no further division
    return int(sum(breakdown.values())), breakdown


def remote_dma_bytes(fn, *args):
    """Per-device IN-KERNEL inter-chip bytes of one call of ``fn(*args)``:
    the remote-DMA payloads a Pallas kernel moves with
    ``make_async_remote_copy`` (ops.ring_buffer.remote_copy), which
    :func:`collective_bytes` cannot see — no collective primitive traces;
    the transfer is a ``dma_start`` equation inside the ``pallas_call``.

    A ``dma_start`` is REMOTE iff it carries a send/recv semaphore PAIR
    (local copies have exactly one DMA semaphore); its payload is the
    source ref's aval.  Multiplicity is a SCHEDULE, not derivable from
    the jaxpr alone; the audit knows the fused-comm ring's contract
    (ops.pallas_gather_ne._gather_solve_ring_kernel): grid ``(row_tiles,
    ring_steps, width_chunks)``, ONE transfer per (row tile, step ``t <=
    S-2``) — the parity-variant ``dma_start``s are mutually exclusive
    ``cond`` arms of that one transfer, so the audit requires them to
    move identical payloads and counts ``grid[0] * (grid[1] - 1)`` fires
    per kernel call, refusing any other grid arity.  A kernel whose
    remote arms disagree on payload is data-dependent traffic → raise,
    same policy as :func:`collective_bytes`'s ``cond`` rule.

    Returns ``(total_bytes, per_call)`` where ``per_call`` lists each
    ``pallas_call``'s contribution (scan-scaled).
    """
    closed = jax.make_jaxpr(fn)(*args)
    per_call = []

    def kernel_remote_payloads(jaxpr, out):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "dma_start":
                sems = [v for v in eqn.invars
                        if "semaphore" in str(getattr(v, "aval", ""))]
                if len(sems) >= 2:
                    out.append(_aval_bytes(eqn.invars[0].aval))
            for p in ("jaxpr", "call_jaxpr", "body_jaxpr", "cond_jaxpr"):
                inner = eqn.params.get(p) if eqn.params else None
                if inner is not None:
                    kernel_remote_payloads(
                        getattr(inner, "jaxpr", inner), out)
            for br in (eqn.params.get("branches", ())
                       if eqn.params else ()):
                kernel_remote_payloads(getattr(br, "jaxpr", br), out)

    def walk(jaxpr, mult):
        for eqn in jaxpr.eqns:
            name = eqn.primitive.name
            if name == "pallas_call":
                payloads = []
                kernel_remote_payloads(eqn.params["jaxpr"], payloads)
                if not payloads:
                    continue
                if len(set(payloads)) != 1:
                    raise ValueError(
                        "remote-DMA arms move different payloads "
                        f"{sorted(set(payloads))} — data-dependent "
                        "traffic is unauditable")
                grid = tuple(eqn.params["grid_mapping"].grid)
                if len(grid) != 3:
                    raise ValueError(
                        f"remote-DMA kernel with grid {grid}: the audit "
                        "only knows the fused-comm ring schedule "
                        "(row_tiles, ring_steps, width_chunks)")
                n_fires = grid[0] * max(0, grid[1] - 1)
                per_call.append(mult * payloads[0] * n_fires)
            elif name == "scan":
                walk(eqn.params["jaxpr"].jaxpr,
                     mult * int(eqn.params["length"]))
            elif name == "cond":
                for br in eqn.params["branches"]:
                    walk(br.jaxpr, mult)
            else:
                for p in ("jaxpr", "call_jaxpr"):
                    inner = eqn.params.get(p) if eqn.params else None
                    if inner is not None:
                        walk(getattr(inner, "jaxpr", inner), mult)

    walk(closed.jaxpr, 1)
    return int(sum(per_call)), per_call
