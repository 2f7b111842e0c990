"""Observability tests: telemetry-off bit parity across the
strategy x compressor x participation x engine matrix, enabled-mode
state-trajectory invariance, ordered progress callbacks and the on_chunk
sink hook, LRU-law-predicted slot-store eviction telemetry, the staleness
histogram under markov departures, the JSONL sink schema round-trip, the
trailing switch-fraction window, the sink registry / leveled-log
contracts, and the stage scopes: every matmul of the local steps names its
model block, and the scopes leave the compiled round unchanged."""
import contextlib
import importlib.util
import json
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.comm import flat, transports
from repro.configs.base import (AsyncConfig, CompressorConfig, FedConfig,
                                FleetConfig, ObsConfig, ScaleConfig,
                                SwitchConfig)
from repro.engine import async_rounds, participation, rounds
from repro.obs import bus, log as obs_log, sinks
from repro.obs import trace as obs_trace
from repro.scale import slots
from repro.tasks import np_classification as npc

EPS = 0.35
N = 8


@pytest.fixture(scope="module")
def np_data():
    key = jax.random.PRNGKey(0)
    (xs, ys), _ = npc.make_dataset(key, n_clients=N)
    return xs, ys


@pytest.fixture(scope="module")
def params(np_data):
    xs, _ = np_data
    return npc.init_params(jax.random.PRNGKey(1), xs.shape[-1])


def _cfg(**kw):
    base = dict(n_clients=N, m=4, local_steps=2, lr=0.1,
                switch=SwitchConfig(mode="hard", eps=EPS),
                uplink=CompressorConfig(kind="topk", ratio=0.5, block=8),
                downlink=CompressorConfig(kind="none"))
    base.update(kw)
    return FedConfig(**base)


def _drive(cfg, params, np_data, T=3, block=0):
    state = rounds.init_state(params, cfg)
    if cfg.async_.enabled:
        state, buf, mets = async_rounds.async_drive(
            state, np_data, npc.loss_pair, cfg, T, block=block)
        return (state, buf), mets, mets.round
    state, mets = rounds.drive(state, np_data, npc.loss_pair, cfg, T,
                               block=block)
    return (state,), mets, mets


def _strip_tel(mets, rm):
    if mets is rm:
        return mets._replace(telemetry=None)
    return mets._replace(round=mets.round._replace(telemetry=None))


# ---------------------------------------------------------------------------
# The parity contract: telemetry off is bit-for-bit the plain engine,
# telemetry on leaves the state trajectory and every shared metric
# bit-identical (observation only)
# ---------------------------------------------------------------------------

PARITY_CASES = [
    dict(strategy="fedsgm",
         uplink=CompressorConfig(kind="topk", ratio=0.5, block=8),
         participation="mask"),
    dict(strategy="fedsgm",
         uplink=CompressorConfig(kind="quant", bits=4, block=8),
         participation="gather",
         downlink=CompressorConfig(kind="quant", bits=8, block=8)),
    dict(strategy="penalty-fedavg",
         uplink=CompressorConfig(kind="none"),
         participation="mask"),
    dict(strategy="fedsgm-soft",
         uplink=CompressorConfig(kind="topk", ratio=0.5, block=8),
         participation="gather",
         async_=AsyncConfig(enabled=True, max_staleness=3, depart=0.3)),
    dict(strategy="fedsgm",
         uplink=CompressorConfig(kind="quant", bits=4, block=8),
         participation="mask",
         async_=AsyncConfig(enabled=True, max_staleness=2, depart=0.3)),
]


class TestTelemetryParity:
    @pytest.mark.parametrize("case", PARITY_CASES,
                             ids=lambda c: "-".join(
                                 [c["strategy"], c["uplink"].kind,
                                  c["participation"],
                                  "async" if "async_" in c else "sync"]))
    def test_enabled_is_observation_only(self, case, params, np_data):
        cfg_off = _cfg(**case)
        cfg_on = cfg_off.replace(obs=ObsConfig(enabled=True, window=4))
        carry0, mets0, rm0 = _drive(cfg_off, params, np_data, T=3, block=2)
        carry1, mets1, rm1 = _drive(cfg_on, params, np_data, T=3, block=2)
        assert rm0.telemetry is None, \
            "disabled telemetry must be the empty pytree subtree"
        assert isinstance(rm1.telemetry, bus.Telemetry)
        for a, b in zip(jax.tree_util.tree_leaves(carry0),
                        jax.tree_util.tree_leaves(carry1)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        for a, b in zip(jax.tree_util.tree_leaves(_strip_tel(mets0, rm0)),
                        jax.tree_util.tree_leaves(_strip_tel(mets1, rm1))):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    def test_margin_and_ratios_match_metrics(self, params, np_data):
        """Telemetry recomputes nothing: the margin is exactly
        ``g_hat - eps`` of the round metrics, and ratios are finite."""
        cfg = _cfg(obs=ObsConfig(enabled=True, window=4))
        _, mets, rm = _drive(cfg, params, np_data, T=4)
        tel = rm.telemetry
        np.testing.assert_array_equal(
            np.asarray(tel.margin), np.asarray(rm.g_hat) - EPS)
        for leaf in jax.tree_util.tree_leaves(tel):
            assert np.isfinite(np.asarray(leaf)).all()

    def test_wire_bytes_match_round_metrics(self, params, np_data):
        """Measured wire bytes in telemetry equal the engine's existing
        accounting (same wire representation): up is whole-round (m
        per-client messages), down is the single broadcast."""
        cfg = _cfg(obs=ObsConfig(enabled=True, window=4),
                   downlink=CompressorConfig(kind="quant", bits=8, block=8))
        _, mets, rm = _drive(cfg, params, np_data, T=3)
        np.testing.assert_array_equal(np.asarray(rm.telemetry.wire_up_bytes),
                                      np.asarray(rm.up_bytes) * cfg.m)
        np.testing.assert_array_equal(
            np.asarray(rm.telemetry.wire_down_bytes),
            np.asarray(rm.down_bytes))


# ---------------------------------------------------------------------------
# Ordered progress + the on_chunk sink hook
# ---------------------------------------------------------------------------

class TestDriveHooks:
    def test_progress_callback_is_ordered(self, params, np_data):
        """ordered=True: progress lines cannot reorder within or across
        scan segments, so the observed round counters are exactly
        1..T in order -- even with obs enabled (tuple carry)."""
        seen = []
        cfg = _cfg(obs=ObsConfig(enabled=True, window=2))
        state = rounds.init_state(params, cfg)
        rounds.drive(state, np_data, npc.loss_pair, cfg, T=6, block=2,
                     progress=lambda t, f, g, s: seen.append(int(t)))
        jax.effects_barrier()
        assert seen == list(range(1, 7))

    def test_progress_ordered_disabled_and_async(self, params, np_data):
        seen = []
        cfg = _cfg(async_=AsyncConfig(enabled=True, max_staleness=2,
                                      depart=0.3))
        state = rounds.init_state(params, cfg)
        async_rounds.async_drive(
            state, np_data, npc.loss_pair, cfg, 5, block=2,
            progress=lambda t, f, g, s: seen.append(int(t)))
        jax.effects_barrier()
        assert seen == list(range(1, 6))

    def test_on_chunk_delivers_block_segments(self, params, np_data):
        chunks = []
        cfg = _cfg(obs=ObsConfig(enabled=True, window=2))
        state = rounds.init_state(params, cfg)
        _, mets = rounds.drive(state, np_data, npc.loss_pair, cfg, T=5,
                               block=2, on_chunk=chunks.append)
        assert [int(np.asarray(c.f).shape[0]) for c in chunks] == [2, 2, 1]
        np.testing.assert_array_equal(
            np.concatenate([np.asarray(c.f) for c in chunks]),
            np.asarray(mets.f))


# ---------------------------------------------------------------------------
# Slot-store telemetry: the LRU law predicts the eviction counters
# ---------------------------------------------------------------------------

def _part(idx, n):
    idx = jnp.asarray(idx, jnp.int32)
    mask = jnp.zeros((n,), jnp.float32).at[idx].set(1.0)
    return participation.Participation(mask, idx, n, int(idx.shape[0]), mask)


class TestSlotTelemetry:
    def test_lru_law_predicts_eviction_telemetry(self):
        """A host-side numpy replica of the LRU allocation law (free
        first, then least-recently-stamped; sampled owners kept) must
        predict occupancy, eviction count and flushed HT mass exactly,
        round by round."""
        n, cap, m, d, T = 12, 4, 3, 32, 10
        ccfg = CompressorConfig(kind="topk", ratio=0.25, block=8)
        ft = flat.FlatTransport(transports.get_transport(ccfg, "packed"),
                                flat.spec_of({"w": jnp.zeros((d,))}))
        store = slots.init(n, cap, d, jnp.float32)
        rng = np.random.RandomState(0)
        int_max = np.iinfo(np.int32).max
        owner = np.full(cap, -1)
        stamp = np.full(cap, -1)
        weight = np.zeros(cap)
        cslot = np.full(n, -1)
        for t in range(T):
            idx = np.sort(rng.choice(n, size=m, replace=False))
            part = _part(idx, n)
            w = np.asarray(jnp.take(participation.agg_weights(part),
                                    jnp.asarray(idx)))
            deltas = jax.random.normal(jax.random.PRNGKey(t), (m, d))
            _, store, stats = slots.transmit(ft, store, deltas, part, t)

            # numpy replica of slots.allocate + the eviction counters
            cur = cslot[idx]
            kept = np.zeros(cap, bool)
            kept[cur[cur >= 0]] = True
            prio = np.where(kept, int_max, np.where(owner < 0, -1, stamp))
            order = np.argsort(prio, kind="stable")
            miss = cur < 0
            rank = np.cumsum(miss) - 1
            claimed = np.where(miss, order[np.clip(rank, 0, None)], cur)
            ev_mask = miss & (owner[claimed] >= 0)
            n_ev, fl_w = int(ev_mask.sum()), float(weight[claimed[ev_mask]]
                                                   .sum())
            cslot[owner[claimed[ev_mask]]] = -1
            owner[claimed] = idx
            stamp[claimed] = t
            weight[claimed] = w
            cslot[idx] = claimed

            assert int(stats.evictions) == n_ev
            assert int(stats.occupancy) == int((owner >= 0).sum())
            np.testing.assert_allclose(float(stats.flush_weight), fl_w,
                                       rtol=1e-6)
            np.testing.assert_array_equal(np.asarray(store.owner), owner)
            np.testing.assert_array_equal(np.asarray(store.client_slot),
                                          cslot)

    def test_engine_surfaces_slot_stats(self, params, np_data):
        """cap >= n: eviction statically absent, telemetry shows zero
        evictions / flush mass and monotone occupancy through the jitted
        drive; cap < n under async reaches full occupancy."""
        cfg = _cfg(participation="gather",
                   scale=ScaleConfig(ef_slots=N),
                   obs=ObsConfig(enabled=True, window=4))
        _, mets, rm = _drive(cfg, params, np_data, T=4)
        tel = rm.telemetry
        assert np.all(np.asarray(tel.slot_evictions) == 0)
        assert np.all(np.asarray(tel.slot_flush_weight) == 0)
        occ = np.asarray(tel.slot_occupancy)
        assert np.all(np.diff(occ) >= 0) and occ.max() <= N

        cfg = _cfg(participation="gather",
                   scale=ScaleConfig(ef_slots=4),
                   async_=AsyncConfig(enabled=True, max_staleness=2,
                                      depart=0.3),
                   obs=ObsConfig(enabled=True, window=4))
        _, mets, rm = _drive(cfg, params, np_data, T=6)
        occ = np.asarray(rm.telemetry.slot_occupancy)
        assert occ.max() <= 4 and occ[-1] == 4


# ---------------------------------------------------------------------------
# Staleness histogram under markov departures
# ---------------------------------------------------------------------------

class TestStalenessHistogram:
    def test_hist_accounts_for_every_parked_entry(self, params, np_data):
        cfg = _cfg(participation="gather",
                   fleet=FleetConfig(sampler="markov"),
                   async_=AsyncConfig(enabled=True, max_staleness=3,
                                      depart=0.4),
                   obs=ObsConfig(enabled=True, window=4))
        state = rounds.init_state(params, cfg)
        _, _, ahist = async_rounds.async_drive(
            state, np_data, npc.loss_pair, cfg, 8, block=4)
        tel = ahist.round.telemetry
        hist = np.asarray(tel.buf_stale_hist)
        assert hist.shape == (8, cfg.async_.max_staleness + 1)
        # every occupied buffer entry lands in exactly one age bin
        np.testing.assert_array_equal(hist.sum(axis=1),
                                      np.asarray(ahist.occupancy))
        np.testing.assert_array_equal(np.asarray(tel.buf_occupancy),
                                      np.asarray(ahist.occupancy))
        np.testing.assert_array_equal(np.asarray(tel.buf_parked_weight),
                                      np.asarray(ahist.buffered_weight))
        # the oldest nonzero bin is the engine's max_age counter
        for t in range(hist.shape[0]):
            if hist[t].sum() > 0:
                assert int(np.nonzero(hist[t])[0].max()) == \
                    int(np.asarray(ahist.max_age)[t])
        assert hist.sum() > 0, "markov departures parked nothing -- the " \
            "test exercised no buffer traffic"

    def test_hist_zero_in_sync_rounds(self, params, np_data):
        cfg = _cfg(obs=ObsConfig(enabled=True, window=4))
        _, _, rm = _drive(cfg, params, np_data, T=3)
        assert np.all(np.asarray(rm.telemetry.buf_stale_hist) == 0)
        assert np.all(np.asarray(rm.telemetry.buf_occupancy) == 0)


# ---------------------------------------------------------------------------
# Trailing switch-fraction window
# ---------------------------------------------------------------------------

class TestSwitchWindow:
    @pytest.mark.parametrize("w", [1, 3, 8])
    def test_window_mean_matches_host_replay(self, w, params, np_data):
        cfg = _cfg(obs=ObsConfig(enabled=True, window=w))
        _, mets, rm = _drive(cfg, params, np_data, T=6, block=2)
        sig = np.asarray(mets.sigma, np.float64)
        want = [sig[max(0, t - w + 1):t + 1].sum() / min(t + 1, w)
                for t in range(len(sig))]
        np.testing.assert_allclose(np.asarray(rm.telemetry.switch_frac),
                                   want, rtol=1e-6)


# ---------------------------------------------------------------------------
# Sinks: registry, JSONL schema round-trip, stdout formatting, log levels
# ---------------------------------------------------------------------------

class TestSinks:
    def test_registry(self):
        assert sinks.sink_names() == ("jsonl", "memory", "stdout")
        with pytest.raises(ValueError, match="unknown metrics sink"):
            sinks.get_sink("nope")

    def test_jsonl_schema_round_trip(self, tmp_path, params, np_data):
        """rows() -> JsonlSink -> json.loads reproduces every record
        exactly (values are python floats/ints: JSON round-trips them
        losslessly), with the meta line split off first."""
        cfg = _cfg(obs=ObsConfig(enabled=True, window=2))
        _, mets, rm = _drive(cfg, params, np_data, T=3)
        recs = sinks.rows(mets, start_round=5, s_per_round=0.5)
        assert [r["round"] for r in recs] == [6, 7, 8]
        assert isinstance(recs[0]["tel_buf_stale_hist"], list)
        path = tmp_path / "m.jsonl"
        sink = sinks.get_sink("jsonl", path=str(path))
        sink.open(meta={"arch": "np"})
        for r in recs:
            sink.emit(r)
        sink.close()
        with open(path) as f:
            lines = [json.loads(line) for line in f]
        assert lines[0] == {"meta": {"arch": "np"}}
        assert lines[1:] == recs

    def test_rows_async_counters(self, params, np_data):
        cfg = _cfg(async_=AsyncConfig(enabled=True, max_staleness=2,
                                      depart=0.3),
                   obs=ObsConfig(enabled=True, window=2))
        _, mets, rm = _drive(cfg, params, np_data, T=3)
        recs = sinks.rows(mets)
        assert all("occupancy" in r and "merged" in r for r in recs)
        np.testing.assert_allclose([r["occupancy"] for r in recs],
                                   np.asarray(mets.occupancy))

    def test_rows_without_telemetry_has_no_tel_keys(self, params, np_data):
        _, mets, _ = _drive(_cfg(), params, np_data, T=2)
        recs = sinks.rows(mets)
        assert not any(k.startswith("tel_") for r in recs for k in r)

    def test_stdout_sink_formats_and_respects_quiet(self, capsys):
        rec = {"round": 3, "f": 1.25, "g_hat": -0.5, "sigma": 1.0,
               "s_per_round": 0.1, "occupancy": 2.0, "tel_margin": -0.85,
               "tel_switch_frac": 0.5, "tel_up_ratio": 0.25}
        sink = sinks.get_sink("stdout")
        old = obs_log.get_level()
        try:
            obs_log.set_level("info")
            sink.emit(rec)
            out = capsys.readouterr().out
            assert out == ("round    3: f=1.2500 g=-0.5000 sigma=1.00 "
                           "(0.10s/round) buffered=2 margin=-0.8500 "
                           "switch=0.50 ef_ratio=0.250\n")
            obs_log.set_level("warning")
            sink.emit(rec)
            assert capsys.readouterr().out == ""
        finally:
            obs_log.set_level(old)

    def test_log_levels(self, capsys):
        old = obs_log.get_level()
        try:
            obs_log.set_level("warning")
            obs_log.log("hidden")
            obs_log.log("shown", level="error")
            out = capsys.readouterr().out
            assert "hidden" not in out and "shown" in out
            with pytest.raises(ValueError, match="unknown log level"):
                obs_log.set_level("loud")
        finally:
            obs_log.set_level(old)


class TestStageScopes:
    """The benchmark's small cells (``perfbench/tests/conftest.py``) built
    by its own harness: the dense model with the unfused full eval, the SSM
    with the fused eval and partial participation, two local steps each."""

    CELLS = {"dense": ("SMALL_DENSE", "smollm-360m", True, {}),
             "ssm": ("SMALL_SSM", "mamba2-130m", False,
                     {"clients": 4, "participating": 2})}

    @staticmethod
    def _compiled_text(which):
        bench = os.path.join(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))), "perfbench")
        spec_ = importlib.util.spec_from_file_location(
            "perfbench_small_cells", os.path.join(bench, "tests",
                                                  "conftest.py"))
        small = importlib.util.module_from_spec(spec_)
        spec_.loader.exec_module(small)     # puts perfbench/ on sys.path
        from harness import program, spec
        model, ref, full_eval, kw = TestStageScopes.CELLS[which]
        cfg = {"name": which, "model": getattr(small, model),
               "federation": small.federation(full_eval)}
        prog = program.Program(cfg, small.mix("quant", local_steps=2, **kw),
                               spec.load_reference(ref, bench))
        prog.state(3)
        return prog.step.as_text()

    @pytest.fixture(scope="class", params=sorted(CELLS))
    def scoped(self, request):
        return request.param, self._compiled_text(request.param)

    def test_local_matmuls_carry_a_model_scope(self, scoped):
        names = re.findall(r" (?:dot|convolution)\(.*op_name=\"([^\"]*)\"",
                           scoped[1])
        local = [n for n in names
                 if "round.eval_round" in n or "round.local_deltas" in n]
        assert local
        assert [n for n in local if "model." not in n] == []

    def test_scopes_leave_the_compiled_round_unchanged(self, scoped,
                                                       monkeypatch):
        which, text = scoped
        monkeypatch.setattr(obs_trace, "stage",
                            lambda name: contextlib.nullcontext())
        jax.clear_caches()
        plain = self._compiled_text(which)
        assert "model.mixer" in text and "model.mixer" not in plain
        assert obs_trace.strip_metadata(text) == \
            obs_trace.strip_metadata(plain)
