"""The flat hot path: contiguous parameter buffers + flat wire codecs.

Why this layer exists (DESIGN.md §Hotpath): the engine round used to run
every elementwise stage -- the E-local-step updates, the per-client delta,
the EF14 residual arithmetic, the server step -- as a ``tree_map`` over the
model pytree, i.e. one kernel launch per leaf per client per step, and the
packed-wire aggregation decompressed clients one at a time in a sequential
``lax.scan``.  This module flattens the model ONCE into a contiguous ``[d]``
buffer with static slice metadata and gives the engine:

* :class:`FlatSpec` / :func:`flatten` / :func:`unflatten` -- the
  pytree <-> ``[d]`` isomorphism.  ``unflatten`` is slices + reshapes (+ a
  dtype cast only for mixed-dtype trees), so ``loss_pair`` still sees the
  exact leaf arrays; every elementwise stage becomes ONE fused operation
  over the buffer, and the uplink EF residual is a single ``[n, d]`` array
  instead of n stacked pytrees,
* :func:`tree_norm` / :func:`project_ball` -- flat norms that reduce each
  leaf *slice* separately (reshaped to the leaf's own shape) and add the
  partials in tree order, so results are bit-for-bit the per-leaf
  ``optim.sgd`` reductions,
* :class:`WireLayout` -- static per-leaf block geometry (offsets, block
  sizes, top-k slots, packed-word counts) with consecutive same-geometry
  leaves merged into *runs*: one pack / kernel call per run instead of per
  leaf x client.  In a large run each block's payload row (its slots, or
  its packed words) is stored padded to a multiple of 128 lanes, so the
  run's ``[*lead, nblocks, width]`` payload view and the flat payload
  buffer share one TPU tiling: at full width the re-tiling copy between
  the two would dominate the round's compile.  Pad entries are zeros and
  are not wire bytes,
* :class:`FlatTransport` -- the flat mirror of :class:`repro.comm.Transport`
  (same ``encode`` / ``reduce`` / ``transmit`` / ``broadcast`` contract, so
  ``engine.participation`` dispatches to it unchanged) with the flat wire
  formats: :class:`FlatPacked` (values + uint16 within-block offsets) for
  the select kinds and :class:`FlatQuant` (b-bit codes bit-packed into
  uint32 words) for the quantizer, and *client-parallel payload-domain
  aggregation* -- a single scatter-add (select) or unpack-multiply-add
  contraction (quant) over the ``[d]`` accumulator replaces the sequential
  per-client scan.

Parity contract: the dense wire (``comm='dense'``) routes the compressor
math through the per-leaf tree operators, so dense-path trajectories are
bit-for-bit the pre-flat engine's; the packed/pallas wires reuse the exact
per-leaf block geometry of the tree packed path (codes / indices round-trip
exactly -- only the aggregation's summation order differs, hence allclose).
"""
from __future__ import annotations

from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.comm import payloads, transports
from repro.comm.payloads import (FlatPacked, FlatQuant, INDEX_DTYPE,
                                 choose_block, pack_codes, unpack_codes,
                                 words_per_block, _SORT_FREE_MIN)
from repro.configs.base import CompressorConfig
from repro.obs import trace as obs_trace
from repro.sharding import partition

tree_map = jax.tree_util.tree_map

# Above this many bytes of participant deltas, the gathered EF encode runs
# one participant at a time (FlatTransport._ef_rows): an [m, d] stack of
# each EF temporary does not fit one chip at full width.  Below it the
# encode stays vectorized over the participants, which keeps it bit-equal
# to the mask path (per-row programs may contract multiply-adds
# differently).
ROW_LOOP_BYTES = 1 << 30


# ---------------------------------------------------------------------------
# FlatSpec: the pytree <-> [d] isomorphism
# ---------------------------------------------------------------------------

class LeafSpec(NamedTuple):
    shape: tuple            # original leaf shape (possibly ())
    dtype: str              # original leaf dtype name
    offset: int             # start in the flat buffer
    size: int               # number of elements


class FlatSpec(NamedTuple):
    """Static metadata of one flattening.  Hashable (treedef + leaf specs),
    so jitted closures capturing a spec retrace only on structure change."""
    treedef: object
    leaves: tuple           # tuple[LeafSpec]
    d: int
    dtype: str              # buffer dtype: the leaves' common promotion
                            # (exact for bf16/f16 sub-lattices of f32)


_SPEC_CACHE: dict = {}


def spec_of(tree) -> FlatSpec:
    """The :class:`FlatSpec` for ``tree`` (cached by structure)."""
    flat, treedef = jax.tree_util.tree_flatten(tree)
    sig = (treedef, tuple((tuple(l.shape), str(jnp.dtype(l.dtype)))
                          for l in flat))
    hit = _SPEC_CACHE.get(sig)
    if hit is not None:
        return hit
    if len(_SPEC_CACHE) > 256:
        _SPEC_CACHE.clear()
    specs, off = [], 0
    for l in flat:
        size = int(np.prod(l.shape, dtype=np.int64)) if len(l.shape) else 1
        specs.append(LeafSpec(tuple(l.shape), str(jnp.dtype(l.dtype)),
                              off, size))
        off += size
    dtype = str(jnp.result_type(*[jnp.dtype(l.dtype) for l in flat])) \
        if flat else "float32"
    spec = FlatSpec(treedef, tuple(specs), off, dtype)
    _SPEC_CACHE[sig] = spec
    return spec


def flatten(spec: FlatSpec, tree) -> jnp.ndarray:
    """Pytree -> contiguous buffer.  Extra *leading* axes shared by every
    leaf (a stacked [n, ...] tree) are preserved: output is [*lead, d]."""
    leaves = jax.tree_util.tree_leaves(tree)
    if len(leaves) != len(spec.leaves):
        raise ValueError(
            f"flatten: tree has {len(leaves)} leaves but the FlatSpec "
            f"records {len(spec.leaves)} -- not the spec'd structure "
            "(payload pytrees cannot be flattened as dense buffers)")
    with obs_trace.stage("flat.flatten"):
        out = []
        for l, ls in zip(leaves, spec.leaves):
            lead = l.shape[:l.ndim - len(ls.shape)]
            out.append(l.astype(spec.dtype).reshape(lead + (ls.size,)))
        return jnp.concatenate(out, axis=-1) if len(out) > 1 else out[0]


def unflatten(spec: FlatSpec, flat: jnp.ndarray):
    """Buffer [*lead, d] -> pytree with leaf shapes [*lead, *leaf_shape].
    Slices + reshapes (a dtype cast only when the tree mixes dtypes)."""
    lead = flat.shape[:-1]
    leaves = []
    with obs_trace.stage("flat.unflatten"):
        for ls in spec.leaves:
            part = flat[..., ls.offset:ls.offset + ls.size]
            leaves.append(part.reshape(lead + ls.shape).astype(ls.dtype))
    return jax.tree_util.tree_unflatten(spec.treedef, leaves)


def tree_norm(spec: FlatSpec, flat: jnp.ndarray) -> jnp.ndarray:
    """sqrt(sum ||leaf||^2): bit-for-bit :func:`repro.optim.sgd.tree_norm`
    of the unflattened tree -- each slice reduces in its own leaf shape and
    the partials add in tree order (a single flat sum associates
    differently)."""
    parts = [jnp.sum(jnp.square(
        flat[ls.offset:ls.offset + ls.size].reshape(ls.shape)
        .astype(jnp.float32))) for ls in spec.leaves]
    return jnp.sqrt(sum(parts))


def project_ball(spec: FlatSpec, flat: jnp.ndarray, radius: float):
    """Flat mirror of :func:`repro.optim.sgd.project_ball` (bit-parity via
    :func:`tree_norm`)."""
    if not radius:
        return flat
    nrm = tree_norm(spec, flat)
    scale = jnp.minimum(1.0, radius / jnp.maximum(nrm, 1e-12))
    return flat * scale


def struct_tree(spec: FlatSpec):
    """ShapeDtypeStruct pytree of the unflattened model (for tree-transport
    wire-bytes delegation and eval_shape plumbing)."""
    leaves = [jax.ShapeDtypeStruct(ls.shape, jnp.dtype(ls.dtype))
              for ls in spec.leaves]
    return jax.tree_util.tree_unflatten(spec.treedef, leaves)


# ---------------------------------------------------------------------------
# WireLayout: static block geometry over the flat buffer
# ---------------------------------------------------------------------------

class LeafWire(NamedTuple):
    offset: int             # flat offset of the leaf
    lead: int               # product of leading dims (blocks run last-axis)
    D: int                  # last-axis size
    block: int              # chosen block size
    nblocks: int            # lead * (D // block)
    k: int                  # select kinds: slots per block
    sort_free: bool         # giant leaf: threshold selection regime
    ks: int = 0             # stored slots per block (its run's)


class RunSpec(NamedTuple):
    """A maximal run of consecutive leaves sharing (block, k, regime): one
    contiguous flat span processed as a single [nblocks, block] view."""
    offset: int
    span: int
    block: int
    nblocks: int
    k: int
    sort_free: bool
    koff: int               # cumulative slot offset in the stored payload
    boff: int               # cumulative block offset (quant scales)
    woff: int               # cumulative word offset in the stored payload
    W: int                  # words per block on the wire
    ks: int                 # stored slots per block (k, or k padded to 128
                            # lanes in a large run)
    Ws: int                 # stored words per block (likewise for W)


class WireLayout(NamedTuple):
    leaves: tuple           # tuple[LeafWire]
    runs: tuple             # tuple[RunSpec]
    K_total: int            # stored slots (pad included)
    NB_total: int
    W_total: int            # stored words (pad included)
    K_wire: int             # slots on the wire
    W_wire: int             # words on the wire


# A run's payload rows are stored padded to 128 lanes once one client's
# stored payload of the run reaches this many entries: a small run re-tiles
# in no time, and unpadded rows keep payload shapes (hence the wire frame
# signatures) distinct across compressor geometries.
PAD_ROWS_FROM = 1 << 16


def _stored(nblocks: int, width: int) -> int:
    if nblocks * width < PAD_ROWS_FROM:
        return width
    return -(-width // 128) * 128


_LAYOUT_CACHE: dict = {}


def wire_layout(spec: FlatSpec, cfg: CompressorConfig) -> WireLayout:
    sig = (spec, cfg)
    hit = _LAYOUT_CACHE.get(sig)
    if hit is not None:
        return hit
    if len(_LAYOUT_CACHE) > 256:
        _LAYOUT_CACHE.clear()
    bits = cfg.bits if cfg.kind == "quant" else 8
    pw_bits = bits if bits in payloads.PACK_BITS else 8
    lws = []
    for ls in spec.leaves:
        D = ls.shape[-1] if len(ls.shape) else 1
        lead = ls.size // D
        b = choose_block(D, cfg.block, cfg.shards)
        k = max(1, min(b, int(round(b * cfg.ratio))))
        lws.append(LeafWire(ls.offset, lead, D, b, lead * (D // b), k,
                            ls.size > _SORT_FREE_MIN))
    runs, members = [], []
    for i, lw in enumerate(lws):
        if runs and runs[-1].block == lw.block and runs[-1].k == lw.k \
                and runs[-1].sort_free == lw.sort_free:
            r = runs[-1]
            runs[-1] = r._replace(span=r.span + lw.lead * lw.D,
                                  nblocks=r.nblocks + lw.nblocks)
            members[-1].append(i)
        else:
            W = words_per_block(lw.block, pw_bits)
            runs.append(RunSpec(lw.offset, lw.lead * lw.D, lw.block,
                                lw.nblocks, lw.k, lw.sort_free,
                                0, 0, 0, W, 0, 0))
            members.append([i])
    koff = boff = woff = kw = ww = 0
    for j, r in enumerate(runs):
        ks, Ws = _stored(r.nblocks, r.k), _stored(r.nblocks, r.W)
        runs[j] = r._replace(koff=koff, boff=boff, woff=woff, ks=ks, Ws=Ws)
        for i in members[j]:
            lws[i] = lws[i]._replace(ks=ks)
        koff += r.nblocks * ks
        boff += r.nblocks
        woff += r.nblocks * Ws
        kw += r.nblocks * r.k
        ww += r.nblocks * r.W
    out = WireLayout(tuple(lws), tuple(runs), koff, boff, woff, kw, ww)
    _LAYOUT_CACHE[sig] = out
    return out


def _run_view(flat: jnp.ndarray, r: RunSpec) -> jnp.ndarray:
    """[*lead, span] slice reshaped to [*lead, nblocks, block]."""
    lead = flat.shape[:-1]
    with obs_trace.stage("comm.relayout"):
        return flat[..., r.offset:r.offset + r.span].reshape(
            lead + (r.nblocks, r.block))


def _rows(payload: jnp.ndarray, off: int, nblocks: int,
          width: int) -> jnp.ndarray:
    """A run's stored payload rows: [*lead, nblocks * width] slice at
    ``off`` viewed as [*lead, nblocks, width]."""
    lead = payload.shape[:-1]
    with obs_trace.stage("comm.relayout"):
        return payload[..., off:off + nblocks * width].reshape(
            lead + (nblocks, width))


def _pad_rows(x: jnp.ndarray, width: int) -> jnp.ndarray:
    """Zero-pad the last axis of [*lead, nblocks, w] payload rows to the
    stored ``width`` and flatten the run to [*lead, nblocks * width]."""
    with obs_trace.stage("comm.relayout"):
        if x.shape[-1] < width:
            x = jnp.pad(x, [(0, 0)] * (x.ndim - 1)
                        + [(0, width - x.shape[-1])])
        return x.reshape(x.shape[:-2] + (x.shape[-2] * width,))


def _cat(xs):
    if len(xs) == 1:
        return xs[0]
    with obs_trace.stage("comm.relayout"):
        return jnp.concatenate(xs, axis=-1)


# ---------------------------------------------------------------------------
# Flat wire codecs (one per packed payload format)
# ---------------------------------------------------------------------------

class _SelectCodec:
    """FlatPacked (values + uint16 offsets) for the block-select kinds."""

    per_client_keys = False
    fused_ef = False

    def __init__(self, cfg: CompressorConfig, spec: FlatSpec,
                 layout: WireLayout, pallas: bool = False):
        self.cfg, self.spec, self.layout, self.pallas = \
            cfg, spec, layout, pallas

    def pack(self, buf: jnp.ndarray, key=None) -> FlatPacked:
        """[*lead, d] -> FlatPacked [*lead, K_total]; one selection op (or
        one ``topk_block`` kernel launch) per run over its
        [*lead, nblocks, block] view."""
        vs, js = [], []
        for r in self.layout.runs:
            blocks = _run_view(buf, r)
            if self.pallas and r.k < r.block:
                from repro.kernels.topk_block import block_topk
                # slot rows come padded to 128 lanes; a small run stores k
                vals, idx = block_topk(blocks, r.k)
                with obs_trace.stage("comm.relayout"):
                    vals = vals[..., :r.ks]
                    idx = idx[..., :r.ks].astype(INDEX_DTYPE)
            else:
                vals, idx = payloads.select_topk_blocks(blocks, r.k,
                                                        r.sort_free)
            vs.append(_pad_rows(vals, r.ks))
            js.append(_pad_rows(idx, r.ks))
        return FlatPacked(_cat(vs), _cat(js))

    def decode(self, p: FlatPacked) -> jnp.ndarray:
        """FlatPacked -> dense [*lead, d] (zeros off-support)."""
        lead = p.values.shape[:-1]
        outs = []
        for r in self.layout.runs:
            vals = _rows(p.values, r.koff, r.nblocks, r.ks)[..., :r.k]
            idx = _rows(p.indices, r.koff, r.nblocks, r.ks)[..., :r.k]
            dense = jnp.zeros(lead + (r.nblocks, r.block), p.values.dtype)
            dense = jnp.put_along_axis(dense, idx.astype(jnp.int32), vals,
                                       axis=-1, inplace=False)
            outs.append(dense.reshape(lead + (r.span,)))
        return outs[0] if len(outs) == 1 else jnp.concatenate(outs, axis=-1)

    def reduce(self, p: FlatPacked, weights: jnp.ndarray, m) -> jnp.ndarray:
        """Client-parallel payload-domain aggregation through the tuned
        bucketed kernel: per run, the stacked (value, within-block offset)
        streams contract as dense per-destination-block buckets
        (:func:`repro.kernels.ops.scatter_agg`) -- no sequential per-client
        dense decompression, and no serialized general scatter."""
        from repro.kernels import ops
        outs = []
        for r in self.layout.runs:
            # the pad slots hold zero values: the kernel reads the stored
            # rows whole and skips them, the XLA plans take the k real ones
            vals = _rows(p.values, r.koff, r.nblocks, r.ks)
            idx = _rows(p.indices, r.koff, r.nblocks, r.ks)
            acc = ops.scatter_agg(vals, idx, weights, block=r.block, k=r.k)
            with obs_trace.stage("comm.relayout"):
                outs.append(acc.reshape(r.span))
        out = outs[0] if len(outs) == 1 else jnp.concatenate(outs)
        return out.astype(self.spec.dtype) / m

    def wire_bytes(self) -> int:
        itemsize = jnp.dtype(self.spec.dtype).itemsize
        return int(self.layout.K_wire
                   * (itemsize + jnp.dtype(INDEX_DTYPE).itemsize))


class _RandkCodec(_SelectCodec):
    """Rand-k shares the FlatPacked format/decode/reduce; packing draws the
    per-leaf PRNG streams of the tree packed path (bitwise-equal payloads),
    so it stays a per-leaf loop under a per-client vmap."""

    per_client_keys = True

    def pack(self, buf: jnp.ndarray, key=None) -> FlatPacked:
        assert key is not None, "randk needs a PRNG key"
        keys = jax.random.split(key, len(self.spec.leaves))
        vs, js = [], []
        for ls, lw, k_leaf in zip(self.spec.leaves, self.layout.leaves, keys):
            leaf = buf[ls.offset:ls.offset + ls.size].reshape(
                ls.shape if ls.shape else (1,))
            p = payloads.block_randk_pack(leaf, self.cfg, k_leaf)
            rows = lambda x: x.reshape(lw.nblocks, lw.k)  # noqa: E731
            vs.append(_pad_rows(rows(p.values), lw.ks))
            js.append(_pad_rows(rows(p.indices), lw.ks))
        return FlatPacked(jnp.concatenate(vs), jnp.concatenate(js))


class _QuantCodec:
    """FlatQuant (bit-packed uint32 words + per-block scales); reduce is the
    fused unpack-multiply-add contraction over the client axis."""

    per_client_keys = False
    fused_ef = False

    def __init__(self, cfg: CompressorConfig, spec: FlatSpec,
                 layout: WireLayout, pallas: bool = False):
        self.cfg, self.spec, self.layout, self.pallas = \
            cfg, spec, layout, pallas
        self.levels = float(2 ** (cfg.bits - 1) - 1)

    def pack(self, buf: jnp.ndarray, key=None) -> FlatQuant:
        ws, ss = [], []
        for r in self.layout.runs:
            blocks = _run_view(buf, r)
            codes, scale = payloads.quant_blocks(blocks, self.cfg.bits)
            words = pack_codes(codes.astype(jnp.int32), self.cfg.bits)
            ws.append(_pad_rows(words, r.Ws))
            ss.append(scale[..., 0].astype(jnp.float32))
        return FlatQuant(_cat(ws), _cat(ss))

    def decode(self, q: FlatQuant) -> jnp.ndarray:
        lead = q.words.shape[:-1]
        outs = []
        for r in self.layout.runs:
            words = _rows(q.words, r.woff, r.nblocks, r.Ws)[..., :r.W]
            scale = q.scale[..., r.boff:r.boff + r.nblocks][..., None]
            codes = unpack_codes(words, self.cfg.bits, r.block)
            vals = codes.astype(self.spec.dtype) / self.levels * scale
            vals = jnp.where(scale > 0, vals, 0.0)
            outs.append(vals.reshape(lead + (r.span,)))
        return outs[0] if len(outs) == 1 else jnp.concatenate(outs, axis=-1)

    def reduce(self, q: FlatQuant, weights: jnp.ndarray, m) -> jnp.ndarray:
        from repro.kernels import ops, tune
        # the pallas backend pins the fused unpack_mma kernel (documented
        # backend semantics); others take the tuner's plan for the shape
        plan = tune.Plan("pallas") if self.pallas else None
        outs = []
        for r in self.layout.runs:
            words = _rows(q.words, r.woff, r.nblocks, r.Ws)
            scale = q.scale[:, r.boff:r.boff + r.nblocks]
            acc = ops.quant_agg(words, scale, weights, self.cfg.bits,
                                r.block, plan=plan)
            with obs_trace.stage("comm.relayout"):
                outs.append(acc.reshape(r.span))
        out = outs[0] if len(outs) == 1 else jnp.concatenate(outs)
        return out.astype(self.spec.dtype) / m

    def wire_bytes(self) -> int:
        return int(4 * (self.layout.W_wire + self.layout.NB_total))


class _QuantPallasCodec(_QuantCodec):
    """Quant on the pallas backend: the EF14 step runs fused in the
    ``quantize_ef_pack`` kernel -- quantizer, residual update AND wire-word
    packing in one pass over each VMEM-resident block (one launch per run,
    the client axis folded into the grid)."""

    fused_ef = True

    def ef(self, e: jnp.ndarray, deltas: jnp.ndarray):
        """(e, deltas) [*lead, d] -> (FlatQuant msgs, e_new [*lead, d])."""
        from repro.kernels.quantize_ef_pack import quantize_ef_pack
        lead = deltas.shape[:-1]
        ws, ss, es = [], [], []
        for r in self.layout.runs:
            # [*lead, nblocks, block] views: the client axis stays a batch
            # axis of the kernel grid (merging it into the block rows
            # would re-tile the whole buffer on a TPU)
            words, scale, e_new = quantize_ef_pack(
                _run_view(e, r), _run_view(deltas, r), self.cfg.bits)
            # word rows come padded to 128 lanes; a small run stores W
            with obs_trace.stage("comm.relayout"):
                ws.append(words[..., :r.Ws].reshape(
                    lead + (r.nblocks * r.Ws,)))
                es.append(e_new.reshape(lead + (r.span,)))
            ss.append(scale)
        return FlatQuant(_cat(ws), _cat(ss)), _cat(es)

    def pack(self, buf: jnp.ndarray, key=None) -> FlatQuant:
        msg, _ = self.ef(jnp.zeros_like(buf), buf)
        return msg


def _make_codec(t: transports.Transport, spec: FlatSpec):
    """The flat wire codec for a tree transport, or None for a dense wire.

    Dense wires (ref backend, ``natural``, quant at non-packable bit widths)
    route the compressor math through the per-leaf tree operators -- flat
    messages are dense [d] buffers and trajectories stay bit-for-bit the
    tree path's."""
    if t.backend == "ref" or t.kind in ("none", "natural"):
        return None
    layout = wire_layout(spec, t.cfg)
    pallas = t.backend == "pallas"
    if t.kind == "topk":
        return _SelectCodec(t.cfg, spec, layout, pallas)
    if t.kind == "randk":
        return _RandkCodec(t.cfg, spec, layout, pallas=False)
    if t.kind == "quant":
        if t.cfg.bits not in payloads.PACK_BITS:
            return None         # unpackable width: dense-wire fallback
        if pallas:
            return _QuantPallasCodec(t.cfg, spec, layout, pallas=True)
        return _QuantCodec(t.cfg, spec, layout, pallas=False)
    return None


# ---------------------------------------------------------------------------
# FlatTransport: the engine-facing flat mirror of comm.Transport
# ---------------------------------------------------------------------------

class FlatTransport:
    """One direction of the wire path over flat [d] buffers.

    Same call-site contract as :class:`repro.comm.Transport` (``encode`` /
    ``encode_gathered`` / ``reduce`` / ``transmit`` / ``transmit_gathered``
    / ``broadcast``), so ``engine.participation`` dispatches to either
    interchangeably; ``e``/``deltas`` are [n, d] arrays, messages are flat
    payloads, and ``like`` is accepted for signature compatibility but the
    static :class:`FlatSpec` supplies all shape information.

    Usage::

        >>> spec = spec_of(params)
        >>> up = FlatTransport(get_transport(cfg, "packed"), spec)
        >>> v_bar, e_new = up.transmit(e, deltas, mask, m, like=None)

    ``cohorts > 1`` turns :meth:`reduce` into the hierarchical two-tier
    aggregation (DESIGN.md §Scale): the stacked client rows split into k
    contiguous cohorts, each edge reducer runs the single-tier
    payload-domain reduce on its cohort, and the server sums the k edge
    partials left-to-right.  ``cohorts=1`` IS the single-tier op
    (bit-parity by construction); select-payload partials are exact
    re-associations of the same weighted scatter-add, quant's
    unpack-multiply-add is a reordered sum (allclose).
    """

    def __init__(self, t: transports.Transport, spec: FlatSpec,
                 cohorts: int = 1):
        self.cfg = t.cfg
        self.kind = t.kind
        self.backend = t.backend
        self.spec = spec
        self.cohorts = max(1, int(cohorts))
        self.codec = _make_codec(t, spec)
        if self.codec is None and t.kind == "quant" and t.backend != "ref":
            # dense-wire fallback for quant at a non-packable bit width on
            # the packed/pallas backends: the compress math must come from
            # the ref transport -- the packed one emits payload pytrees
            # (which a dense flat message cannot carry) and the pallas one
            # assumes the stacked-kernel entry points.  Identical values:
            # both equal the dense quantizer bit-for-bit.
            t = transports.get_transport(t.cfg, "ref")
        self.t = t

    # -- capability flags (delegated) ---------------------------------------

    @property
    def is_identity(self) -> bool:
        return self.t.is_identity

    @property
    def needs_residual(self) -> bool:
        return self.t.needs_residual

    @property
    def tracks_center(self) -> bool:
        return self.t.tracks_center

    @property
    def needs_key(self) -> bool:
        return self.t.needs_key

    @property
    def wire(self) -> str:
        return "dense" if self.codec is None else "packed"

    # -- wire primitives ----------------------------------------------------

    def compress(self, buf: jnp.ndarray, key: Optional[jax.Array] = None):
        """Flat message for one [d] buffer (the operator C)."""
        if self.is_identity:
            return buf
        if self.codec is None:
            return flatten(self.spec,
                           self.t.compress(unflatten(self.spec, buf), key))
        return self.codec.pack(buf, key)

    def decompress(self, message, like=None) -> jnp.ndarray:
        if self.codec is None:
            return message
        return self.codec.decode(message)

    def wire_bytes(self, like=None) -> int:
        """True wire bytes of one message: packed formats count their
        materialized arrays (uint32 words, uint16 offsets); dense wires
        delegate to the tree transport's measured accounting."""
        if self.codec is None:
            return self.t.wire_bytes(struct_tree(self.spec))
        return self.codec.wire_bytes()

    # -- round-level call sites --------------------------------------------

    def _ef_clients(self, e, deltas, key, keys=None):
        with obs_trace.stage("comm.ef_encode"):
            return self._ef_clients_inner(e, deltas, key, keys)

    def _ef_clients_inner(self, e, deltas, key, keys=None):
        if self.codec is not None and self.codec.fused_ef:
            return self.codec.ef(e, deltas)
        buf = e + deltas if e is not None else deltas
        if self.codec is None:
            n = deltas.shape[0]
            if self.needs_key and key is not None:
                if keys is None:
                    keys = jax.random.split(key, n)
                msgs = jax.vmap(self.compress)(buf, keys)
            else:
                msgs = jax.vmap(lambda r: self.compress(r))(buf)
            return msgs, buf - msgs
        if self.codec.per_client_keys:
            n = deltas.shape[0]
            if keys is None:
                keys = jax.random.split(key, n)
            msgs = jax.vmap(self.codec.pack)(buf, keys)
        else:
            msgs = self.codec.pack(buf)
        return msgs, buf - self.codec.decode(msgs)

    def encode(self, e, deltas, mask, like=None,
               key: Optional[jax.Array] = None):
        """Per-client EF14 encode over the [n, d] stacks, no aggregation
        (mirrors ``Transport.encode``; the staleness buffer parks rows of
        the returned wire-format messages)."""
        if self.is_identity:
            return partition.constrain_flat(deltas), e
        msgs, e_stack = self._ef_clients(e, deltas, key)
        e_out = e
        if e is not None:
            e_stack = partition.constrain_flat(
                partition.constrain_leading(e_stack, "client"))
            e_out = transports.mask_where(mask, e_stack, e)
        if self.wire == "dense":
            msgs = partition.constrain_leading(msgs, "client")
        return msgs, e_out

    def encode_gathered(self, e, deltas, idx, mask, like=None,
                        key: Optional[jax.Array] = None):
        """Compute-sparse encode: ``deltas`` holds the m participants' rows;
        per-client results (incl. PRNG streams) match the mask path's."""
        n = mask.shape[0]
        if self.is_identity:
            return transports.scatter_rows(deltas, idx, n), e
        keys = None
        if self.needs_key and key is not None:
            keys = jnp.take(jax.random.split(key, n), idx, axis=0)
        if e is not None and self.codec is not None \
                and deltas.size * deltas.dtype.itemsize > ROW_LOOP_BYTES:
            msgs, e_out = self._ef_rows(e, deltas, idx, key, keys)
            e_out = partition.constrain_leading(e_out, "client")
        else:
            e_part = None if e is None else transports.take_rows(e, idx)
            msgs, e_stack = self._ef_clients(e_part, deltas, key, keys=keys)
            e_out = e if e is None else partition.constrain_leading(
                transports.put_rows(e, idx, e_stack), "client")
        msgs = transports.scatter_rows(msgs, idx, n)
        if self.wire == "dense":
            msgs = partition.constrain_leading(msgs, "client")
        return msgs, e_out

    def _ef_rows(self, e, deltas, idx, key, keys):
        """EF14 encode of the m participants one at a time: row ``j`` reads
        the residual ``e[idx[j]]`` and writes its new residual back in
        place, so the round holds one participant's EF temporaries, not an
        [m, d] stack of each (which does not fit one chip at full width).
        A repeated id (a realized cohort padded by ``mask_indices``) takes
        the message and residual of its first occurrence, as the
        vectorized encode of identical rows would.  Returns (stacked
        [m, ...] messages, updated e)."""
        m = deltas.shape[0]

        def row(x, j):
            with obs_trace.stage("comm.rows"):
                return jax.lax.dynamic_slice_in_dim(x, j, 1, axis=0)

        def encode_row(e_j, d_j, k_j):
            return self._ef_clients(e_j, d_j, key, keys=k_j)

        k0 = None if keys is None else keys[:1]
        shapes, _ = jax.eval_shape(encode_row, e[:1], deltas[:1], k0)
        msgs0 = tree_map(lambda s: jnp.zeros((m,) + s.shape[1:], s.dtype),
                         shapes)

        def body(j, carry):
            msgs, e = carry
            e_j = row(e, idx[j])
            msg_j, e_new = encode_row(e_j, row(deltas, j),
                                      None if keys is None else row(keys, j))
            first = jnp.argmax(idx == idx[j])
            dup = first < j
            msg_j = tree_map(lambda M, x: jnp.where(dup, row(M, first), x),
                             msgs, msg_j)
            e_new = jnp.where(dup, e_j, e_new.astype(e.dtype))
            with obs_trace.stage("comm.rows"):
                msgs = tree_map(
                    lambda M, x: jax.lax.dynamic_update_slice_in_dim(
                        M, x, j, 0), msgs, msg_j)
                return msgs, jax.lax.dynamic_update_slice_in_dim(
                    e, e_new, idx[j], 0)
        return jax.lax.fori_loop(0, m, body, (msgs0, e))

    def reduce_single(self, msgs, weights, m, like=None) -> jnp.ndarray:
        """The single-tier weighted aggregation of stacked wire messages
        into [d]: a mask contraction (dense), scatter-add (select payloads)
        or unpack-multiply-add (quant words) over the client axis -- never
        a sequential per-client scan.  This is one edge reducer of the
        two-tier mode (and the whole of :meth:`reduce` at ``cohorts=1``)."""
        with obs_trace.stage("comm.reduce"):
            if self.wire == "dense":
                return jnp.tensordot(weights.astype(msgs.dtype), msgs,
                                     axes=(0, 0)) / m
            return partition.constrain_flat(
                self.codec.reduce(msgs, weights, m))

    def reduce(self, msgs, weights, m, like=None) -> jnp.ndarray:
        """Weighted aggregation of stacked wire messages into [d]; with
        ``cohorts=k > 1`` the hierarchical two-tier form -- k edge
        reductions over contiguous client cohorts, their partials summed
        left-to-right (the async StaleBuffer merge composes unchanged:
        both its reduce call sites land here)."""
        k = self.cohorts
        if k <= 1:
            return self.reduce_single(msgs, weights, m, like)
        rows = weights.shape[0]
        if rows % k:
            raise ValueError(
                f"two-tier aggregation: {rows} stacked payload rows do not "
                f"split into {k} equal cohorts -- ScaleConfig.cohorts must "
                "divide the client-row count")
        csize = rows // k
        acc = None
        for c in range(k):
            sl = slice(c * csize, (c + 1) * csize)
            sub = tree_map(lambda x: x[sl], msgs)
            part = self.reduce_single(sub, weights[sl], m, like)
            acc = part if acc is None else acc + part
        return acc

    def transmit(self, e, deltas, mask, m, like=None,
                 key: Optional[jax.Array] = None):
        if self.is_identity:
            return self.reduce(deltas, mask, m), e
        msgs, e_out = self.encode(e, deltas, mask, like, key)
        return self.reduce(msgs, mask, m), e_out

    def transmit_gathered(self, e, deltas, idx, mask, m, like=None,
                          key: Optional[jax.Array] = None):
        if self.is_identity:
            dense = transports.scatter_rows(deltas, idx, mask.shape[0])
            return self.reduce(dense, mask, m), e
        msgs, e_out = self.encode_gathered(e, deltas, idx, mask, like, key)
        return self.reduce(msgs, mask, m), e_out

    def broadcast(self, w: jnp.ndarray, x_new: jnp.ndarray,
                  key: Optional[jax.Array] = None) -> jnp.ndarray:
        """Primal-EF21 downlink on flat buffers: w' = w + C(x_new - w)."""
        if self.is_identity:
            return x_new
        with obs_trace.stage("comm.broadcast"):
            msg = self.compress(x_new - w, key)
            return w + self.decompress(msg)


def flat_transports_for(cfg, spec: FlatSpec):
    """(uplink, downlink) :class:`FlatTransport` pair for a FedConfig.

    ``cfg.scale.cohorts`` configures the uplink's two-tier aggregation;
    the downlink is one broadcast (no client axis), so it never tiers."""
    backend = transports.backend_for(cfg.comm)
    k = getattr(getattr(cfg, "scale", None), "cohorts", 1)
    return (FlatTransport(transports.get_transport(cfg.uplink, backend), spec,
                          cohorts=k),
            FlatTransport(transports.get_transport(cfg.downlink, backend),
                          spec))
