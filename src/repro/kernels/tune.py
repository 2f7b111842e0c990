"""Aggregation-kernel autotuner: per-shape implementation plans.

Every aggregation entry point in :mod:`repro.kernels.ops` (``scatter_agg``,
``quant_agg``, ``segment_rows``, and the fused ``quantize_ef_pack`` path)
consults this module for a :class:`Plan` -- which implementation to run and
with what tile parameters -- before tracing.  Plans are memoized in-process
and persisted to ``.pallas_tune.json`` so repeated runs (and CI) never
re-time.

Cache-key contract
------------------
A plan is keyed by ``kind | backend | shape-signature`` where

* ``kind`` names the entry point (``scatter_agg``, ``quant_agg``,
  ``segment_rows``, ``ef_pack``),
* ``backend`` is ``jax.default_backend()`` (``cpu``/``gpu``/``tpu``) -- a
  cache tuned on one backend is never consulted on another, so moving the
  run to a new accelerator re-tunes (or re-seeds) automatically, and
* the shape signature is built from *abstract* shapes only (n, nblocks, k,
  block, bits, ...) -- never from array values -- so a key is stable across
  seeds and the plan lookup adds no tracing inputs.

First use of an unseen key falls back to the deterministic seeded default
for the backend (below) and records it; an explicit ``--sweep`` times the
candidate space on the host and overwrites the entry with the measured
winner.  ``--seed`` writes the defaults for the standard benchmark shapes
without timing anything, which is what CI runs to stay deterministic.

Seeded defaults
---------------
* ``scatter_agg``: CPU -> factored one-hot GEMM (``gemm``, chunk=8; XLA
  serializes general scatter-add on CPU, the batched matmul over the
  split H x L one-hot factors is ~4x faster than the scan at n=64/d=132k,
  with the plain ``onehot`` contraction as the simpler runner-up); TPU ->
  the Pallas bucketed kernel; GPU -> native ``scatter`` (XLA emits
  parallel atomics there).
* ``quant_agg``: CPU -> ``tensordot`` over unpacked codes; TPU -> the
  fused ``unpack_mma`` Pallas kernel.
* ``segment_rows``: CPU -> XLA ``.at[].set`` scatter (unique segment ids,
  already parallel enough); TPU -> the Pallas segment-sum kernel.
* ``ef_pack``: CPU -> jnp quantize+pack; TPU -> fused Pallas kernel.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import pathlib
import threading
from typing import Any, Dict

import jax

CACHE_ENV = "REPRO_TUNE_CACHE"
_DEFAULT_CACHE = ".pallas_tune.json"
_VERSION = 1

_lock = threading.Lock()
_plans: Dict[str, "Plan"] | None = None
_dirty = False


@dataclasses.dataclass(frozen=True)
class Plan:
    """A tuned choice: implementation name + static tile parameters."""
    impl: str
    params: Dict[str, Any] = dataclasses.field(default_factory=dict)


def cache_path() -> pathlib.Path:
    return pathlib.Path(os.environ.get(CACHE_ENV, _DEFAULT_CACHE))


def key_for(kind: str, backend: str | None = None, **sig: Any) -> str:
    backend = backend or jax.default_backend()
    parts = ",".join(f"{k}={sig[k]}" for k in sorted(sig))
    return f"{kind}|{backend}|{parts}"


def _seed_plan(kind: str, backend: str) -> Plan:
    if kind == "scatter_agg":
        if backend == "tpu":
            return Plan("pallas", {"rows": 8})
        if backend == "gpu":
            return Plan("scatter")
        return Plan("gemm", {"chunk": 8})
    if kind == "quant_agg":
        return Plan("pallas" if backend == "tpu" else "tensordot")
    if kind == "segment_rows":
        if backend == "tpu":
            return Plan("pallas", {"crows": 8, "cd": 512})
        return Plan("xla")
    if kind == "ef_pack":
        return Plan("pallas" if backend == "tpu" else "jnp")
    raise KeyError(f"unknown tuner kind: {kind!r}")


def _load() -> Dict[str, Plan]:
    global _plans
    if _plans is None:
        _plans = {}
        path = cache_path()
        if path.exists():
            try:
                raw = json.loads(path.read_text())
            except (OSError, json.JSONDecodeError):
                raw = {}
            if raw.get("version") == _VERSION:
                for k, v in raw.get("plans", {}).items():
                    _plans[k] = Plan(v["impl"], dict(v.get("params", {})))
    return _plans


def save() -> None:
    """Persist the in-memory plan table (no-op when nothing changed)."""
    global _dirty
    with _lock:
        if not _dirty or _plans is None:
            return
        payload = {
            "version": _VERSION,
            "plans": {k: {"impl": p.impl, "params": p.params}
                      for k, p in sorted(_plans.items())},
        }
        try:
            cache_path().write_text(json.dumps(payload, indent=1) + "\n")
            _dirty = False
        except OSError:
            pass


def get_plan(kind: str, **sig: Any) -> Plan:
    """Plan for ``kind`` at this shape signature on the current backend.

    Unseen keys seed the backend default and mark the cache dirty; callers
    running long jobs may :func:`save` afterwards to persist."""
    global _dirty
    backend = jax.default_backend()
    key = key_for(kind, backend, **sig)
    with _lock:
        plans = _load()
        plan = plans.get(key)
        if plan is None:
            plan = _seed_plan(kind, backend)
            plans[key] = plan
            _dirty = True
    return plan


def table() -> Dict[str, Plan]:
    """Every plan resolved or loaded in this process, by cache key."""
    with _lock:
        return dict(_load())


def put_plan(kind: str, plan: Plan, **sig: Any) -> None:
    global _dirty
    key = key_for(kind, jax.default_backend(), **sig)
    with _lock:
        _load()[key] = plan
        _dirty = True


def reset(clear_file: bool = False) -> None:
    """Drop the in-memory table (tests); optionally delete the file too."""
    global _plans, _dirty
    with _lock:
        _plans, _dirty = None, False
    if clear_file:
        try:
            cache_path().unlink()
        except OSError:
            pass


# Standard shapes seeded for CI (the BENCH_hotpath aggregation workload
# n=64 / d=132097 under topk ratio=0.25 block=128 and quant4 block=128).
_SEED_SIGS = [
    ("scatter_agg", dict(n=64, nblocks=1032, k=32, block=128)),
    ("quant_agg", dict(n=64, nblocks=1033, W=16, bits=4, block=128)),
    ("segment_rows", dict(m=64, n=64)),
    ("ef_pack", dict(nblocks=1033, block=128, bits=4)),
]


def seed_defaults() -> int:
    """Write deterministic backend defaults for the standard shapes."""
    for kind, sig in _SEED_SIGS:
        get_plan(kind, **sig)
    save()
    return len(_SEED_SIGS)


def _time(fn, *args, iters: int = 3) -> float:
    import time
    out = fn(*args)
    jax.block_until_ready(out)
    t0 = time.perf_counter()
    for _ in range(iters):
        out = fn(*args)
        jax.block_until_ready(out)
    return (time.perf_counter() - t0) / iters


def sweep_scatter_agg(n: int = 64, nblocks: int = 1032, k: int = 32,
                      block: int = 128) -> Plan:
    """Time the select-aggregation candidates on this host and persist
    the winner for the given shape."""
    import jax.numpy as jnp
    from repro.kernels import ops

    key = jax.random.PRNGKey(0)
    vals = jax.random.normal(key, (n, nblocks, k), jnp.float32)
    idx = jax.random.randint(jax.random.fold_in(key, 1),
                             (n, nblocks, k), 0, block).astype(jnp.uint16)
    w = jnp.ones((n,), jnp.float32) / n
    candidates = [Plan("scatter")]
    for chunk in (4, 8, 16, 32):
        candidates.append(Plan("gemm", {"chunk": chunk}))
        candidates.append(Plan("onehot", {"chunk": chunk}))
    if jax.default_backend() == "tpu":
        for rows in (4, 8, 16):
            candidates.append(Plan("pallas", {"rows": rows}))
    best, best_t = None, float("inf")
    for plan in candidates:
        t = _time(lambda v, i, ww, p=plan:
                  ops.scatter_agg(v, i, ww, block=block, plan=p),
                  vals, idx, w)
        print(f"  scatter_agg {plan.impl} {plan.params}: {t * 1e6:.0f}us")
        if t < best_t:
            best, best_t = plan, t
    put_plan("scatter_agg", best, n=n, nblocks=nblocks, k=k, block=block)
    save()
    return best


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--seed", action="store_true",
                    help="write deterministic backend defaults (CI mode)")
    ap.add_argument("--sweep", action="store_true",
                    help="time candidates on this host and persist winners")
    args = ap.parse_args(argv)
    if args.seed:
        wrote = seed_defaults()
        print(f"seeded {wrote} plans for backend={jax.default_backend()} "
              f"-> {cache_path()}")
    if args.sweep:
        plan = sweep_scatter_agg()
        print(f"scatter_agg winner: {plan.impl} {plan.params}")
    if not (args.seed or args.sweep):
        ap.print_help()
        return 2
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
