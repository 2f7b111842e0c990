"""Fault-tolerance benchmark: recovery latency and sustained rounds/sec
for the wire runtime under real SIGKILL chaos (ISSUE 10, DESIGN.md §Wire
fault tolerance).

Seeds BENCH_faults.json: one record per kill rate {0, 0.1, 0.25} over a
4-worker subprocess fleet -- rounds/sec, respawn/recovery counts, and
the death-to-recovery latency p50/p95.  The rate-0 row is the price of
*arming* the runtime (heartbeats + liveness sweeps, no faults): compare
against BENCH_wire.json's plain 4-worker row for the overhead.

``--smoke`` is the CI guard (the ``faults-smoke`` job):

1. kill + recover parity -- a thread-spawn run where one worker dies
   mid-eval is respawned (EF re-seeded from the round snapshot, round
   replayed) and stays BIT-identical to the clean single-process oracle;
2. degraded invariants -- with the respawn budget at zero the run
   demotes the dead worker's clients and must conserve the HT mass of
   every realized round, reject nothing, and degrade at least once;
3. real-signal leg -- a 2-process run eats one actual SIGKILL and
   completes all rounds with zero rejected frames.

``--soak`` is the acceptance gate: a 40-round 4-worker subprocess run at
kill rate 0.25 (every phase entry draws; the supervisor respawns within
budget) that must complete with zero rejected/corrupted merges and the
HT mass conserved on every realized round.

    PYTHONPATH=src python -m benchmarks.faults_bench [--smoke|--soak]
                                                     [--out F.json]
"""
from __future__ import annotations

import argparse
import json
import sys
import tempfile
import time

import numpy as np

from repro.wire.coordinator import wire_drive
from repro.wire.supervisor import WireFaultConfig

from benchmarks.wire_bench import _cfg, _oracle

DEFAULT_OUT = "BENCH_faults.json"

# effectively unbounded: the soak measures sustained recovery, not the
# budget cliff (the budget path is pinned in tests/test_wire_faults.py)
SOAK_RESPAWNS = 10 ** 6


def _ht_masses(stats, T):
    """float32 HT mass of every realized round the coordinator recorded."""
    return [np.float32(np.asarray(stats.realized[t]["weights"],
                                  np.float32).sum())
            for t in range(T) if t in stats.realized]


def _check_invariants(stats, T, label):
    if stats.totals["rejected"]:
        raise AssertionError(
            f"{label}: {stats.totals['rejected']} rejected frames "
            f"(corrupted merges) -- totals {stats.totals}")
    masses = _ht_masses(stats, T)
    if masses and any(m != masses[0] for m in masses):
        raise AssertionError(f"{label}: HT mass not conserved: {masses}")
    return masses


def chaos_record(rate: float, n=8, T=12, workers=4, seed=1,
                 deadline=180.0) -> dict:
    """One soak row: T subprocess rounds at per-phase SIGKILL rate
    ``rate``, invariants asserted, latencies summarized."""
    fed = _cfg(n=n)
    # heartbeat_timeout must out-wait the first-round jit compile: a
    # subprocess worker can hold the GIL for seconds while compiling, and
    # a tight 3x-heartbeat default would respawn perfectly healthy
    # workers (SIGKILL death is caught by the socket EOF regardless, so
    # the generous timeout does not slow kill recovery)
    faults = WireFaultConfig(heartbeat_s=0.5, heartbeat_timeout=20.0,
                             min_quorum=0.25, max_respawns=SOAK_RESPAWNS,
                             eval_grace=30.0, respawn_window=60.0)
    proc_chaos = {"kill": rate, "phase": "any"} if rate > 0 else None
    with tempfile.TemporaryDirectory() as d:
        t0 = time.perf_counter()
        _, mets, stats = wire_drive(
            fed, T, workers=workers, spawn="process", deadline=deadline,
            faults=faults, proc_chaos=proc_chaos, chaos_seed=seed,
            ckpt_dir=d, ckpt_every=1)
        wall = time.perf_counter() - t0
    assert len(np.asarray(mets.f)) == T
    _check_invariants(stats, T, f"kill_rate={rate}")
    if rate > 0:
        # a seed whose uniforms never cross the rate would measure a
        # chaos-free run and mislabel it (random.Random(0) does exactly
        # that at 0.25 x 24 draws) -- an unexercised row must fail loudly
        assert stats.totals["respawns"] > 0, \
            f"kill_rate={rate} drew no kills; pick another seed"
    rec_ms = [1e3 * s for s in stats.recovery_s]
    rec = {
        "kill_rate": rate, "rounds": T, "workers": workers, "n": n,
        "rounds_per_s": round(T / wall, 3),
        "wall_s": round(wall, 3),
        "respawns": stats.totals["respawns"],
        "recovered": stats.totals["recovered"],
        "degraded_rounds": stats.totals["degraded"],
        "recovery_ms_p50": round(float(np.percentile(rec_ms, 50)), 1)
        if rec_ms else 0.0,
        "recovery_ms_p95": round(float(np.percentile(rec_ms, 95)), 1)
        if rec_ms else 0.0,
    }
    print(f"faults_k{rate},{1e6 * wall / T:.1f},"
          f"rounds_per_s={rec['rounds_per_s']};"
          f"respawns={rec['respawns']};"
          f"recovery_ms_p50={rec['recovery_ms_p50']}")
    return rec


def faults_records(rates=(0.0, 0.1, 0.25), **kw):
    return [chaos_record(rate, **kw) for rate in rates]


def smoke(T=3) -> int:
    fed = _cfg()
    die = {"die_round": 1, "die_phase": "eval"}

    # 1) kill + recover: respawn + EF re-seed + replay == clean oracle
    with tempfile.TemporaryDirectory() as d:
        st_w, mets_w, stats = wire_drive(
            fed, T, workers=2, spawn="thread", chaos=[None, dict(die)],
            deadline=60.0,
            faults=WireFaultConfig(max_respawns=1, eval_grace=30.0,
                                   respawn_window=60.0),
            ckpt_dir=d, ckpt_every=1)
    st_o, mets_o = _oracle(fed, T)
    import jax
    for a, b in zip(jax.tree_util.tree_leaves((st_o.w, st_o.e_up, st_o.key)),
                    jax.tree_util.tree_leaves((st_w.w, st_w.e_up, st_w.key))):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    for fname in ("f", "g_hat", "g_full", "sigma", "feasible", "f_full"):
        np.testing.assert_array_equal(
            np.asarray(getattr(mets_o, fname)),
            np.asarray(getattr(mets_w, fname)))
    assert stats.totals["respawns"] == 1 and stats.totals["recovered"] == 1
    print("smoke: kill mid-eval -> respawn -> bit-parity with clean "
          "oracle .. ok")

    # 2) budget 0: degraded rounds conserve HT mass, reject nothing
    with tempfile.TemporaryDirectory() as d:
        _, _, stats = wire_drive(
            fed, T, workers=2, spawn="thread", chaos=[None, dict(die)],
            deadline=60.0,
            faults=WireFaultConfig(max_respawns=0, eval_grace=2.0),
            ckpt_dir=d, ckpt_every=1)
    _check_invariants(stats, T, "degraded")
    assert stats.totals["degraded"] > 0, stats.totals
    print(f"smoke: degraded rounds x{stats.totals['degraded']} "
          f"(HT mass conserved, 0 rejected) .. ok")

    # 3) one real SIGKILL over subprocesses
    faults = WireFaultConfig(heartbeat_s=0.5, heartbeat_timeout=20.0,
                             max_respawns=2, eval_grace=30.0,
                             respawn_window=60.0)
    with tempfile.TemporaryDirectory() as d:
        _, mets_p, stats_p = wire_drive(
            fed, T, workers=2, spawn="process", deadline=180.0,
            faults=faults,
            proc_chaos={"kill": 1.0, "max_kills": 1, "phase": "eval"},
            ckpt_dir=d, ckpt_every=1)
    assert len(np.asarray(mets_p.f)) == T
    _check_invariants(stats_p, T, "sigkill")
    assert stats_p.totals["respawns"] >= 1, stats_p.totals
    print(f"smoke: real SIGKILL leg (respawns="
          f"{stats_p.totals['respawns']}, recovered="
          f"{stats_p.totals['recovered']}, degraded="
          f"{stats_p.totals['degraded']}) .. ok")
    print("smoke: ok")
    return 0


def soak(T=40, workers=4, rate=0.25, seed=1) -> int:
    """The acceptance gate (see module docstring)."""
    rec = chaos_record(rate, T=T, workers=workers, seed=seed,
                       deadline=600.0)
    print(f"soak: {T} rounds x {workers} workers at kill rate {rate}: "
          f"{rec['respawns']} respawns, {rec['degraded_rounds']} degraded "
          f"rounds, 0 rejected, HT mass conserved .. ok")
    return 0


def faults_table(out: str = DEFAULT_OUT, **kw):
    records = faults_records(**kw)
    with open(out, "w") as f:
        json.dump({"bench": "faults", "records": records}, f, indent=1)
    return records


ALL = [faults_table]


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="CI guard (recover parity + degraded invariants "
                         "+ one real SIGKILL)")
    ap.add_argument("--soak", action="store_true",
                    help="acceptance soak (40 rounds, 4 workers, kill "
                         "rate 0.25)")
    ap.add_argument("--rounds", type=int, default=12,
                    help="rounds per table row (soak uses 40)")
    ap.add_argument("--out", default=DEFAULT_OUT)
    args = ap.parse_args()
    if args.smoke:
        sys.exit(smoke())
    if args.soak:
        sys.exit(soak())
    print("name,us_per_call,derived")
    records = faults_table(out=args.out, T=args.rounds)
    print(f"wrote {args.out} ({len(records)} records)", file=sys.stderr)


if __name__ == "__main__":
    main()