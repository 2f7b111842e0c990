"""Flat hot-path tests (ISSUE 5): FlatParams flatten/unflatten round-trips
across every model family (mixed dtypes included), bit-packed wire
pack->unpack exactness for bits in {2, 4, 8} at non-word-multiple block
sizes, flat-vs-tree transport parity (exact code/index round-trip, parallel
payload-domain aggregation), the fused eval/step-1 path, the gated
delta_norm metric, and the switch_blend kernel parity guard."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro import comm
from repro.comm import flat, payloads, transports
from repro.configs.base import CompressorConfig, FedConfig, SwitchConfig
from repro.engine import rounds
from repro.kernels.quantize_ef_pack import quantize_ef_pack
from repro.kernels.unpack_mma import unpack_mma
from repro.tasks import np_classification as npc

EPS = 0.35
N = 10


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
    base = dict(n_clients=N, m=5, local_steps=2, lr=0.1,
                switch=SwitchConfig(mode="hard", eps=EPS),
                uplink=CompressorConfig(kind="none"),
                downlink=CompressorConfig(kind="none"))
    base.update(kw)
    return FedConfig(**base)


def _traj(cfg, params, batches, T=3):
    state = rounds.init_state(params, cfg)
    step = jax.jit(lambda s, b: rounds.round_step(s, b, npc.loss_pair, cfg))
    mets = []
    for _ in range(T):
        state, m = step(state, batches)
        mets.append(m)
    return state, mets


# ---------------------------------------------------------------------------
# Bit-packed wire words
# ---------------------------------------------------------------------------

class TestPackedWords:
    @settings(max_examples=20, deadline=None)
    @given(bits=st.sampled_from([2, 4, 8]),
           block=st.integers(1, 200), seed=st.integers(0, 2 ** 16))
    def test_pack_unpack_bit_exact(self, bits, block, seed):
        """Round-trip exactness for every packable width, including block
        sizes that are not multiples of the 32//bits lanes-per-word."""
        L = 2 ** (bits - 1) - 1
        rng = np.random.RandomState(seed)
        codes = rng.randint(-L, L + 1, size=(3, block))
        words = payloads.pack_codes(jnp.asarray(codes), bits)
        assert words.dtype == jnp.uint32
        assert words.shape[-1] == payloads.words_per_block(block, bits)
        back = payloads.unpack_codes(words, bits, block)
        np.testing.assert_array_equal(np.asarray(back), codes)

    def test_unpackable_width_raises(self):
        with pytest.raises(ValueError, match="not packable"):
            payloads.pack_codes(jnp.zeros((2, 8), jnp.int32), 6)

    @settings(max_examples=10, deadline=None)
    @given(bits=st.sampled_from([2, 4, 8]),
           block=st.sampled_from([5, 12, 32, 33]), seed=st.integers(0, 999))
    def test_fused_kernel_words_match_jnp_pack(self, bits, block, seed):
        """quantize_ef_pack emits bit-for-bit the words of quantize +
        payloads.pack_codes (word rows zero-padded to 128 lanes), and the
        residual of the unfused EF step."""
        key = jax.random.PRNGKey(seed)
        e = jax.random.normal(key, (4, block))
        d = jax.random.normal(jax.random.fold_in(key, 1), (4, block))
        words, scale, e_new = quantize_ef_pack(e, d, bits)
        W = payloads.words_per_block(block, bits)
        assert words.shape == (4, -(-W // 128) * 128)
        assert not np.asarray(words[:, W:]).any()
        words, scale = words[:, :W], scale[:, None]
        buf = e + d
        sc = jnp.max(jnp.abs(buf), axis=-1, keepdims=True)
        L = float(2 ** (bits - 1) - 1)
        safe = jnp.where(sc > 0, sc, 1.0)
        codes = jnp.where(sc > 0, jnp.round(buf / safe * L),
                          0.0).astype(jnp.int32)
        np.testing.assert_array_equal(
            np.asarray(words), np.asarray(payloads.pack_codes(codes, bits)))
        np.testing.assert_array_equal(np.asarray(scale), np.asarray(sc))
        v = jnp.where(sc > 0, codes.astype(jnp.float32) / L * sc, 0.0)
        np.testing.assert_allclose(np.asarray(e_new), np.asarray(buf - v),
                                   atol=5e-7, rtol=0)

    def test_unpack_mma_matches_dense_reduction(self):
        key = jax.random.PRNGKey(3)
        n, nb, block, bits = 5, 4, 24, 4
        L = float(2 ** (bits - 1) - 1)
        codes = jax.random.randint(key, (n, nb, block), -7, 8)
        scale = jax.random.uniform(jax.random.fold_in(key, 1), (n, nb)) + 0.1
        wt = jax.random.uniform(jax.random.fold_in(key, 2), (n,))
        words = payloads.pack_codes(codes, bits)
        acc = unpack_mma(words, scale, wt, bits, block)
        dense = codes.astype(jnp.float32) / L * scale[..., None]
        ref = jnp.tensordot(wt, dense, axes=(0, 0))
        np.testing.assert_allclose(np.asarray(acc), np.asarray(ref),
                                   rtol=2e-5, atol=1e-6)


# ---------------------------------------------------------------------------
# FlatParams round-trip
# ---------------------------------------------------------------------------

class TestFlatSpec:
    FAMILY_CONFIGS = ("smollm-360m", "deepseek-v2-236b", "mamba2-130m",
                      "recurrentgemma-2b", "whisper-small")

    @pytest.mark.parametrize("name", FAMILY_CONFIGS)
    def test_roundtrip_every_model_family(self, name):
        """flatten -> unflatten is the identity (values, shapes, dtypes) on
        real model parameter pytrees of every registered family."""
        from repro import configs
        from repro.models import build
        cfg = configs.get_reduced(name)
        fns = build(cfg)
        params = fns.init(jax.random.PRNGKey(0), cfg)
        spec = flat.spec_of(params)
        buf = flat.flatten(spec, params)
        assert buf.ndim == 1 and buf.shape[0] == spec.d
        back = flat.unflatten(spec, buf)
        la, lb = (jax.tree_util.tree_leaves(params),
                  jax.tree_util.tree_leaves(back))
        assert len(la) == len(lb)
        for a, b in zip(la, lb):
            assert a.dtype == b.dtype and a.shape == b.shape
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    def test_roundtrip_mixed_dtypes(self):
        """bf16/f16 leaves promote exactly into the f32 buffer and cast back
        exactly (sub-lattices of f32), preserving per-leaf dtypes."""
        key = jax.random.PRNGKey(0)
        tree = {"a": jax.random.normal(key, (17, 3)),
                "b": jax.random.normal(key, (33,)).astype(jnp.bfloat16),
                "c": jax.random.normal(key, ()).astype(jnp.float16)}
        spec = flat.spec_of(tree)
        assert jnp.dtype(spec.dtype) == jnp.float32
        back = flat.unflatten(spec, flat.flatten(spec, tree))
        for k in tree:
            assert back[k].dtype == tree[k].dtype
            np.testing.assert_array_equal(np.asarray(tree[k]),
                                          np.asarray(back[k]))

    def test_stacked_roundtrip(self):
        key = jax.random.PRNGKey(1)
        tree = {"w": jax.random.normal(key, (4, 8, 3)),
                "b": jax.random.normal(key, (4,))}   # [n=4] stacked
        spec = flat.spec_of({"w": tree["w"][0], "b": tree["b"][0]})
        buf = flat.flatten(spec, tree)
        assert buf.shape == (4, spec.d)
        back = flat.unflatten(spec, buf)
        np.testing.assert_array_equal(np.asarray(back["w"]),
                                      np.asarray(tree["w"]))

    def test_norm_and_projection_bit_parity(self):
        from repro.optim import sgd
        key = jax.random.PRNGKey(2)
        tree = {"a": 3.0 * jax.random.normal(key, (41, 7)),
                "b": jax.random.normal(key, (13,))}
        spec = flat.spec_of(tree)
        buf = flat.flatten(spec, tree)
        assert float(sgd.tree_norm(tree)) == float(flat.tree_norm(spec, buf))
        proj_t = sgd.project_ball(tree, 0.5)
        proj_f = flat.project_ball(spec, buf, 0.5)
        np.testing.assert_array_equal(
            np.asarray(flat.flatten(spec, proj_t)), np.asarray(proj_f))


# ---------------------------------------------------------------------------
# Flat transport parity vs the tree wire stack
# ---------------------------------------------------------------------------

def _mlp_tree(key):
    return {"W1": jax.random.normal(key, (24, 16)),
            "b1": jnp.asarray(0.5),
            "W2": jax.random.normal(jax.random.fold_in(key, 1), (16,)),
            "s": jax.random.normal(jax.random.fold_in(key, 2), (3, 8))}


class TestFlatTransportParity:
    CASES = (("topk", "packed"), ("topk", "pallas"), ("randk", "packed"),
             ("quant", "packed"), ("quant", "pallas"), ("topk", "ref"),
             ("quant", "ref"), ("natural", "ref"))

    def _compressor(self, kind):
        return {"topk": CompressorConfig(kind="topk", ratio=0.25, block=8),
                "randk": CompressorConfig(kind="randk", ratio=0.25, block=8),
                "quant": CompressorConfig(kind="quant", bits=4, block=8),
                "natural": CompressorConfig(kind="natural")}[kind]

    def test_row_loops_match_gather_and_scatter(self, monkeypatch):
        """Long rows move by dynamic-slice loops (ROW_LOOP_ELEMS); they
        give XLA's row gather and scatter exactly."""
        key = jax.random.PRNGKey(0)
        x = jax.random.normal(key, (6, 10))
        rows = jax.random.normal(jax.random.fold_in(key, 1), (3, 10))
        idx = jnp.asarray([4, 1, 3], jnp.int32)
        want = (jnp.take(x, idx, axis=0), x.at[idx].set(rows))
        monkeypatch.setattr(transports, "ROW_LOOP_ELEMS", 0)
        got = jax.jit(lambda x_, r_: (transports.take_rows(x_, idx),
                                      transports.put_rows(x_, idx, r_)))(
            x, rows)
        for a, b in zip(want, got):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    @pytest.mark.parametrize("kind,backend", [("topk", "pallas"),
                                              ("quant", "pallas"),
                                              ("randk", "packed")])
    def test_gathered_encode_one_participant_at_a_time(self, kind, backend,
                                                       monkeypatch):
        """The row-at-a-time gathered encode (taken above ROW_LOOP_BYTES of
        deltas) gives the vectorized encode's messages and residuals,
        repeated ids (a padded realized cohort) included."""
        key = jax.random.PRNGKey(0)
        spec = flat.spec_of(_mlp_tree(key))
        ft = flat.FlatTransport(
            transports.get_transport(self._compressor(kind), backend), spec)
        n = 6
        e = jax.random.normal(jax.random.fold_in(key, 1), (n, spec.d))
        idx = jnp.asarray([1, 3, 4, 1], jnp.int32)
        deltas = jax.random.normal(jax.random.fold_in(key, 2), (4, spec.d))
        deltas = deltas.at[3].set(deltas[0])      # the repeat's own row
        mask = jnp.zeros((n,)).at[idx].set(1.0)
        kk = jax.random.PRNGKey(9)
        enc = jax.jit(lambda e_, d_: ft.encode_gathered(e_, d_, idx, mask,
                                                        key=kk))
        want = enc(e, deltas)
        monkeypatch.setattr(flat, "ROW_LOOP_BYTES", 0)
        got = jax.jit(lambda e_, d_: ft.encode_gathered(e_, d_, idx, mask,
                                                        key=kk))(e, deltas)
        for a, b in zip(jax.tree_util.tree_leaves(want),
                        jax.tree_util.tree_leaves(got)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=1e-6, atol=1e-6)

    @pytest.mark.parametrize("kind,backend", [("topk", "packed"),
                                              ("topk", "pallas"),
                                              ("randk", "packed"),
                                              ("quant", "packed"),
                                              ("quant", "pallas")])
    def test_lane_padded_payload_rows_transmit_alike(self, kind, backend,
                                                     monkeypatch):
        """Large runs store each block's payload row padded to 128 lanes
        (PAD_ROWS_FROM); the padded layout transmits the same aggregate
        and residuals, and counts the same wire bytes."""
        key = jax.random.PRNGKey(0)
        spec = flat.spec_of(_mlp_tree(key))
        cfg = self._compressor(kind)
        t = transports.get_transport(cfg, backend)
        n = 6
        deltas = jax.random.normal(jax.random.fold_in(key, 3), (n, spec.d))
        e = jax.random.normal(jax.random.fold_in(key, 4), (n, spec.d))
        mask = jnp.asarray([1, 0, 1, 1, 0, 1], jnp.float32)
        kk = jax.random.PRNGKey(9)

        def run():
            flat._LAYOUT_CACHE.clear()
            ft = flat.FlatTransport(t, spec)
            out = jax.jit(lambda d_: ft.transmit(e, d_, mask, 4, key=kk))(
                deltas)
            return out, ft.wire_bytes(), ft.codec.layout

        (v0, e0), bytes0, lay0 = run()
        monkeypatch.setattr(flat, "PAD_ROWS_FROM", 0)
        (v1, e1), bytes1, lay1 = run()
        flat._LAYOUT_CACHE.clear()
        assert all(r.ks % 128 == 0 and r.Ws % 128 == 0 for r in lay1.runs)
        assert lay1.K_total > lay0.K_total
        assert bytes1 == bytes0
        np.testing.assert_array_equal(np.asarray(e0), np.asarray(e1))
        np.testing.assert_allclose(np.asarray(v0), np.asarray(v1),
                                   rtol=1e-6, atol=1e-7)

    def test_select_payload_codes_round_trip_exactly(self):
        """Flat top-k payloads carry the exact values/offsets of the tree
        packed path (same per-leaf block geometry), concatenated in leaf
        order."""
        key = jax.random.PRNGKey(0)
        tree = _mlp_tree(key)
        spec = flat.spec_of(tree)
        cfg = self._compressor("topk")
        t = transports.get_transport(cfg, "packed")
        ft = flat.FlatTransport(t, spec)
        msg_t = t.compress(tree)
        msg_f = ft.compress(flat.flatten(spec, tree))
        leaves = jax.tree_util.tree_leaves(
            msg_t, is_leaf=lambda x: isinstance(x, payloads.PackedLeaf))
        np.testing.assert_array_equal(
            np.concatenate([np.asarray(p.values).reshape(-1)
                            for p in leaves]), np.asarray(msg_f.values))
        np.testing.assert_array_equal(
            np.concatenate([np.asarray(p.indices).reshape(-1)
                            for p in leaves]), np.asarray(msg_f.indices))
        assert msg_f.indices.dtype == jnp.uint16

    @pytest.mark.parametrize("kind,backend", CASES)
    def test_transmit_matches_tree_path(self, kind, backend):
        key = jax.random.PRNGKey(0)
        tree = _mlp_tree(key)
        spec = flat.spec_of(tree)
        t = transports.get_transport(self._compressor(kind), backend)
        ft = flat.FlatTransport(t, spec)
        n = 6
        deltas = jax.random.normal(jax.random.fold_in(key, 3), (n, spec.d))
        e = jnp.zeros((n, spec.d))
        mask = jnp.asarray([1, 0, 1, 1, 0, 1], jnp.float32)
        kk = jax.random.PRNGKey(9)
        e_tree = jax.vmap(lambda r: flat.unflatten(spec, r))(e)
        d_tree = jax.vmap(lambda r: flat.unflatten(spec, r))(deltas)
        v_f, e_f = jax.jit(
            lambda d_: ft.transmit(e, d_, mask, 4, key=kk))(deltas)
        v_t, e_t = jax.jit(
            lambda d_: t.transmit(e_tree, d_, mask, 4, like=tree,
                                  key=kk))(d_tree)
        np.testing.assert_allclose(
            np.asarray(flat.flatten(spec, v_t)), np.asarray(v_f),
            rtol=2e-5, atol=1e-6)
        np.testing.assert_allclose(
            np.asarray(jax.vmap(lambda r: flat.flatten(spec, r))(e_t)),
            np.asarray(e_f), rtol=2e-5, atol=5e-6)

    def test_gathered_matches_mask_bitwise(self):
        key = jax.random.PRNGKey(0)
        spec = flat.spec_of(_mlp_tree(key))
        t = transports.get_transport(self._compressor("topk"), "packed")
        ft = flat.FlatTransport(t, spec)
        n = 6
        deltas = jax.random.normal(jax.random.fold_in(key, 3), (n, spec.d))
        e = 0.01 * jax.random.normal(jax.random.fold_in(key, 4), (n, spec.d))
        mask = jnp.asarray([1, 0, 1, 1, 0, 1], jnp.float32)
        idx = jnp.asarray([0, 2, 3, 5], jnp.int32)
        vm, em = jax.jit(lambda: ft.transmit(e, deltas, mask, 4))()
        vg, eg = jax.jit(lambda: ft.transmit_gathered(
            e, jnp.take(deltas, idx, axis=0), idx, mask, 4))()
        np.testing.assert_array_equal(np.asarray(vm), np.asarray(vg))
        np.testing.assert_array_equal(np.asarray(em), np.asarray(eg))

    @pytest.mark.parametrize("backend", ("packed", "pallas"))
    def test_quant_unpackable_bits_fall_back_dense(self, backend):
        """quant at a non-packable width (bits=16) on the packed/pallas
        backends must keep working via the dense-wire ref fallback --
        regression: the fallback used to route compress through the
        payload-emitting packed transport and crash."""
        key = jax.random.PRNGKey(0)
        tree = _mlp_tree(key)
        spec = flat.spec_of(tree)
        cfg = CompressorConfig(kind="quant", bits=16, block=8)
        ft = flat.FlatTransport(transports.get_transport(cfg, backend), spec)
        assert ft.wire == "dense"
        n = 4
        deltas = jax.random.normal(jax.random.fold_in(key, 1), (n, spec.d))
        e = jnp.zeros((n, spec.d))
        mask = jnp.ones((n,), jnp.float32)
        v, e_new = jax.jit(lambda d: ft.transmit(e, d, mask, n))(deltas)
        t_ref = transports.get_transport(cfg, "ref")
        d_tree = jax.vmap(lambda r: flat.unflatten(spec, r))(deltas)
        e_tree = jax.vmap(lambda r: flat.unflatten(spec, r))(e)
        v_ref, _ = jax.jit(lambda d: t_ref.transmit(
            e_tree, d, mask, n, like=tree))(d_tree)
        np.testing.assert_array_equal(
            np.asarray(flat.flatten(spec, v_ref)), np.asarray(v))

    def test_flatten_rejects_mismatched_structure(self):
        key = jax.random.PRNGKey(0)
        spec = flat.spec_of(_mlp_tree(key))
        with pytest.raises(ValueError, match="leaves"):
            flat.flatten(spec, {"only": jnp.zeros((3,))})

    def test_quant_wire_bytes_are_true_bit_packed_size(self):
        """4-bit quant moves d/2 code bytes (packed uint32 words) + one fp32
        scale per block -- the acceptance wire-size criterion."""
        key = jax.random.PRNGKey(0)
        tree = {"w": jax.random.normal(key, (1024,))}
        spec = flat.spec_of(tree)
        cfg = CompressorConfig(kind="quant", bits=4, block=128)
        ft = flat.FlatTransport(transports.get_transport(cfg, "packed"), spec)
        nblocks = 1024 // 128
        assert ft.wire_bytes() == 1024 // 2 + 4 * nblocks
        msg = ft.compress(flat.flatten(spec, tree))
        assert msg.words.dtype == jnp.uint32
        assert payloads.packed_bytes(msg) == ft.wire_bytes()


# ---------------------------------------------------------------------------
# Engine integration: fused eval, lean metrics, packed engine parity
# ---------------------------------------------------------------------------

class TestEngineHotpath:
    def test_fused_eval_matches_unfused_state(self, np_data, params):
        """full_eval=False engages the fused vjp eval/step-1 path; the STATE
        trajectory must be bit-for-bit the unfused implementation's (the
        metric values may differ by an ulp -- batched-vs-shared forward)."""
        cfg = _cfg(participation="gather", full_eval=False,
                   uplink=CompressorConfig(kind="topk", ratio=0.25, block=8),
                   downlink=CompressorConfig(kind="topk", ratio=0.25,
                                             block=8))
        s_fused, m_fused = _traj(cfg, params, np_data)

        # unfused reference: force the separate-eval path by overriding the
        # strategy's local_objective hook (identical math, opts out of the
        # blend_values fusion)
        from repro.engine import strategies as strat_mod

        class _Unfused(strat_mod.FedSGM):
            name = "fedsgm-unfused-test"

            def local_objective(self, loss_pair, sigma, cfg):
                def obj(p, b):
                    f, g = loss_pair(p, b)
                    return self.blend_values(f, g, sigma, cfg)
                return obj

        strat_mod.register_strategy(_Unfused)
        try:
            s_ref, m_ref = _traj(cfg.replace(strategy=_Unfused.name),
                                 params, np_data)
        finally:
            strat_mod._STRATEGIES.pop(_Unfused.name, None)
        for a, b in zip(jax.tree_util.tree_leaves(s_fused),
                        jax.tree_util.tree_leaves(s_ref)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        np.testing.assert_allclose(
            float(m_fused[-1].f), float(m_ref[-1].f), rtol=1e-5)

    def test_fused_full_eval_mask_matches_unfused(self, np_data, params):
        """Full-participation mask mode evaluates exactly the local-step
        rows (all n), so the fused vjp path now also covers full_eval=True
        there (ISSUE 6 satellite).  The state trajectory must stay
        bit-identical to the explicit separate-eval implementation.
        Partial-participation mask mode intentionally stays unfused (the
        mask-vs-gather parity oracle compares eval programs bit-for-bit at
        m < n -- see compute_round)."""
        cfg = _cfg(m=N, participation="mask", full_eval=True,
                   uplink=CompressorConfig(kind="topk", ratio=0.25, block=8),
                   downlink=CompressorConfig(kind="topk", ratio=0.25,
                                             block=8))
        s_fused, m_fused = _traj(cfg, params, np_data)

        from repro.engine import strategies as strat_mod

        class _Unfused(strat_mod.FedSGM):
            name = "fedsgm-unfused-mask-test"

            def local_objective(self, loss_pair, sigma, cfg):
                def obj(p, b):
                    f, g = loss_pair(p, b)
                    return self.blend_values(f, g, sigma, cfg)
                return obj

        strat_mod.register_strategy(_Unfused)
        try:
            s_ref, m_ref = _traj(cfg.replace(strategy=_Unfused.name),
                                 params, np_data)
        finally:
            strat_mod._STRATEGIES.pop(_Unfused.name, None)
        for a, b in zip(jax.tree_util.tree_leaves(s_fused),
                        jax.tree_util.tree_leaves(s_ref)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        np.testing.assert_allclose(
            float(m_fused[-1].f), float(m_ref[-1].f), rtol=1e-5)

    def test_lean_metrics_gates_delta_norm_only(self, np_data, params):
        cfg = _cfg(uplink=CompressorConfig(kind="topk", ratio=0.25, block=8))
        s_full, m_full = _traj(cfg, params, np_data)
        s_lean, m_lean = _traj(cfg.replace(lean_metrics=True),
                               params, np_data)
        for a, b in zip(jax.tree_util.tree_leaves(s_full),
                        jax.tree_util.tree_leaves(s_lean)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        assert float(m_full[-1].delta_norm) > 0
        assert float(m_lean[-1].delta_norm) == 0.0
        for fld in ("f", "g_hat", "g_full", "sigma", "feasible", "f_full"):
            assert float(getattr(m_full[-1], fld)) == \
                float(getattr(m_lean[-1], fld)), fld

    def test_packed_engine_matches_dense_trajectory(self, np_data, params):
        """Same compressor on the dense vs packed wire: identical math,
        different wire -- trajectories allclose (aggregation order only)."""
        comp = CompressorConfig(kind="quant", bits=8, block=8)
        cfg = _cfg(uplink=comp, downlink=comp)
        s_dense, _ = _traj(cfg, params, np_data)
        s_packed, m_packed = _traj(cfg.replace(comm="packed"),
                                   params, np_data)
        for a, b in zip(jax.tree_util.tree_leaves(s_dense),
                        jax.tree_util.tree_leaves(s_packed)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=1e-5, atol=1e-6)
        # packed-mode up_bytes report the true bit-packed wire size
        spec = flat.spec_of(params)
        ft = flat.FlatTransport(transports.get_transport(comp, "packed"),
                                spec)
        assert float(m_packed[-1].up_bytes) == ft.wire_bytes()

    def test_e_up_is_flat(self, params):
        cfg = _cfg(uplink=CompressorConfig(kind="topk", ratio=0.25, block=8))
        state = rounds.init_state(params, cfg)
        spec = flat.spec_of(params)
        assert state.e_up.shape == (N, spec.d)


# ---------------------------------------------------------------------------
# switch_blend kernel parity (satellite: stop the bit-rot)
# ---------------------------------------------------------------------------

class TestSwitchBlendParity:
    def test_kernel_matches_direct_blend(self):
        """switch_blend is subsumed on the engine hot path (strategies grad
        the blended scalar objective, so no standalone blend op exists to
        route through it -- DESIGN.md §Hotpath); this parity pin keeps the
        kernel correct for direct users of kernels.ops."""
        from repro.kernels.ops import switch_blend_tree
        key = jax.random.PRNGKey(0)
        gf = {"a": jax.random.normal(key, (130, 7)),
              "b": jax.random.normal(jax.random.fold_in(key, 1), (33,))}
        gg = jax.tree_util.tree_map(lambda x: x * 0.3 + 1.0, gf)
        for sigma in (0.0, 0.25, 1.0):
            s = jnp.asarray(sigma)
            out = switch_blend_tree(gf, gg, s, block=64)
            ref = jax.tree_util.tree_map(
                lambda a, b: (1.0 - s) * a + s * b, gf, gg)
            for k in gf:
                np.testing.assert_allclose(np.asarray(out[k]),
                                           np.asarray(ref[k]),
                                           rtol=1e-6, atol=1e-7)
