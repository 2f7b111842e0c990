"""Pluggable transports: one registry entry per compressor kind.

A :class:`Transport` owns the full wire path of one direction of a FedSGM
round: the compressor math (``compress``/``decompress``), the wire
representation (dense simulation or packed payload), exact ``wire_bytes``,
the fused EF14 step ``ef_step(e, delta) -> (message, e_new)``, and the two
round-level call sites used by ``fedsgm.round_step``:

* ``transmit(e, deltas, mask, m, like, key)`` -- per-client EF14 + masked
  aggregation over the (possibly sharded) client axis,
* ``broadcast(w, x_new, key)`` -- the primal-EF21 downlink
  ``w' = w + C(x_new - w)``.

Three selectable backends (``FedConfig.comm`` -> :func:`backend_for`):

* ``ref``    -- pure jnp, the paper-faithful dense simulation (global
  per-leaf top-k, per-client vmap),
* ``packed`` -- only the payload (values/indices or codes/scales) crosses
  the client axis; blockwise selection for top-k AND rand-k/quant,
* ``pallas`` -- hot paths route through the fused TPU kernels: the EF14
  quant step through ``kernels/quantize_ef`` (saves one full HBM round-trip
  of the residual buffer per round) and block top-k selection through
  ``kernels/topk_block``; falls back to ``packed``/``ref`` math where no
  kernel exists (rand-k, natural).
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import CompressorConfig
from repro.comm import payloads
from repro.comm.payloads import (PackedLeaf, QuantPayload, block_geometry,
                                 choose_block)
from repro.obs import trace as obs_trace

tree_map = jax.tree_util.tree_map

BACKENDS = ("ref", "packed", "pallas")

_COMM_TO_BACKEND = {"dense": "ref", "packed": "packed", "pallas": "pallas"}


def backend_for(comm: str) -> str:
    """Map a ``FedConfig.comm`` mode to a transport backend name."""
    try:
        return _COMM_TO_BACKEND[comm]
    except KeyError:
        raise ValueError(
            f"unknown comm mode {comm!r}; expected one of {sorted(_COMM_TO_BACKEND)}")


# -- tree helpers (local to avoid importing repro.optim) --------------------

def _tree_add(a, b):
    return tree_map(jnp.add, a, b)


def _tree_sub(a, b):
    return tree_map(jnp.subtract, a, b)


def _tree_zeros_like(a):
    return tree_map(jnp.zeros_like, a)


def _leading_dim(tree) -> int:
    return jax.tree_util.tree_leaves(tree)[0].shape[0]


def masked_mean(tree, mask, m):
    """Mean over participating clients of a stacked [n, ...] pytree.

    dot-general over the (sharded) client axis => partial reduction stays
    local and only the params-sized result crosses the wire; jnp.sum over a
    sharded axis makes GSPMD all-gather the n-fold stack (EXPERIMENTS.md
    §Perf iteration A0)."""
    return tree_map(
        lambda v: jnp.tensordot(mask.astype(v.dtype), v, axes=(0, 0)) / m,
        tree)


def mask_where(mask, new, old):
    """Per-client row select on stacked [n, ...] pytrees (payload trees
    included): rows with ``mask > 0`` take ``new``, the rest keep ``old``.
    Used for EF-residual gating here and buffer-slot writes in
    engine.async_rounds."""
    n = mask.shape[0]

    def one(en, eo):
        m = mask.reshape((n,) + (1,) * (en.ndim - 1))
        return jnp.where(m > 0, en, eo)
    return tree_map(one, new, old)


_mask_where = mask_where        # internal alias (pre-async name)


# Rows of at least this many elements move by a loop of dynamic row slices
# (take_rows / put_rows): a row gather or scatter of a full-width [n, d]
# buffer lowers on a TPU to one unrolled copy per 32k-element chunk
# (minutes of compile, and a compiler stack overflow at 362M elements).
# Smaller rows keep XLA's gather / scatter.
ROW_LOOP_ELEMS = 1 << 20


def take_rows(x: jnp.ndarray, idx: jnp.ndarray) -> jnp.ndarray:
    """``x[idx]`` for a stacked [n, ...] array and in-range row ids; long
    rows move one dynamic row slice per id (:data:`ROW_LOOP_ELEMS`)."""
    with obs_trace.stage("comm.rows"):
        if x[0].size < ROW_LOOP_ELEMS:
            return jnp.take(x, idx, axis=0)

        def body(j, out):
            row = jax.lax.dynamic_slice_in_dim(x, idx[j], 1, axis=0)
            return jax.lax.dynamic_update_slice_in_dim(out, row, j, axis=0)
        out = jnp.zeros((idx.shape[0],) + x.shape[1:], x.dtype)
        return jax.lax.fori_loop(0, idx.shape[0], body, out)


def put_rows(x: jnp.ndarray, idx: jnp.ndarray,
             rows: jnp.ndarray) -> jnp.ndarray:
    """``x.at[idx].set(rows)`` for in-range row ids (a repeated id writes
    one of its rows); long rows land one dynamic row update per id
    (:data:`ROW_LOOP_ELEMS`)."""
    with obs_trace.stage("comm.rows"):
        if x[0].size < ROW_LOOP_ELEMS:
            return x.at[idx].set(rows.astype(x.dtype))

        def body(j, out):
            row = jax.lax.dynamic_slice_in_dim(rows, j, 1, axis=0)
            return jax.lax.dynamic_update_slice_in_dim(
                out, row.astype(out.dtype), idx[j], axis=0)
        return jax.lax.fori_loop(0, idx.shape[0], body, x)


def scatter_rows(tree, idx, n: int):
    """[m, ...] participant rows -> full [n, ...] layout, zeros elsewhere.
    Works on dense leaves and payload pytrees alike (payload fields carry
    the same leading client axis).  Shared by the gathered transmit path,
    the SlotStore restore and engine.participation.

    Participant ids are unique, so scatter == segment-sum here: float
    leaves route through the tuned :func:`repro.kernels.ops.segment_rows`
    when the backend plan selects the Pallas segment kernel; otherwise
    (and always for integer wire fields -- packed words / offsets must
    round-trip bit-exactly, a float one-hot contraction would not) the rows
    land by :func:`put_rows`.  Under an active mesh they always do: the
    compiler does not partition a Pallas kernel across devices."""
    from repro.kernels import ops, tune
    from repro.sharding import partition

    def one(x):
        if jnp.issubdtype(x.dtype, jnp.floating) \
                and partition.current_mesh() is None:
            plan = tune.get_plan("segment_rows", m=x.shape[0], n=n)
            if plan.impl == "pallas":
                return ops.segment_rows(x, idx, n, plan=plan)
        return put_rows(jnp.zeros((n,) + x.shape[1:], x.dtype), idx, x)
    return tree_map(one, tree)


# ---------------------------------------------------------------------------
# Registry
# ---------------------------------------------------------------------------

_REGISTRY: dict = {}
_WIRE_BYTES_CACHE: dict = {}


def register(cls):
    """Class decorator: register a Transport under its ``kind``."""
    _REGISTRY[cls.kind] = cls
    return cls


def get_transport(cfg: CompressorConfig, backend: str = "ref") -> "Transport":
    """Build the transport for ``cfg.kind`` with the given backend."""
    try:
        cls = _REGISTRY[cfg.kind]
    except KeyError:
        raise ValueError(
            f"unknown compressor kind {cfg.kind!r}; "
            f"registered: {sorted(_REGISTRY)}")
    return cls(cfg, backend)


def transport_kinds() -> tuple:
    return tuple(sorted(_REGISTRY))


# ---------------------------------------------------------------------------
# Base class
# ---------------------------------------------------------------------------

class Transport:
    """One direction of the compressed wire path (see module docstring).

    Law: a transport owns compressor math, wire representation, exact
    ``wire_bytes`` and the fused EF14 step for its direction; the engine
    talks to it only through ``transmit``/``broadcast`` (synchronous
    barrier) or ``encode``/``reduce`` (async buffered rounds).

    Usage::

        >>> t = get_transport(CompressorConfig(kind="topk", ratio=0.1),
        ...                   backend="packed")
        >>> msg = t.compress(delta)            # wire-format payload
        >>> dense = t.decompress(msg, like=delta)
        >>> v_bar, e_new = t.transmit(e, deltas, mask, m, like=params)
    """

    kind: str = "?"
    needs_key: bool = False         # stochastic compressor (randk/natural)

    def __init__(self, cfg: CompressorConfig, backend: str = "ref"):
        if backend not in BACKENDS:
            raise ValueError(f"unknown backend {backend!r}; expected {BACKENDS}")
        self.cfg = cfg
        self.backend = backend

    # -- capability flags ---------------------------------------------------

    @property
    def is_identity(self) -> bool:
        return False

    @property
    def needs_residual(self) -> bool:
        """Uplink EF14 residual state exists only under real compression."""
        return not self.is_identity

    @property
    def tracks_center(self) -> bool:
        """Downlink EF21 stores the server center x separately from w."""
        return not self.is_identity

    @property
    def wire(self) -> str:
        """'packed' when the payload (not dense tensors) crosses the client
        axis; 'dense' for the paper-faithful simulation."""
        return "dense"

    # -- wire-level primitives (unstacked pytrees) --------------------------

    def compress(self, tree, key: Optional[jax.Array] = None):
        """Wire message for a dense pytree (the operator C of Assumption
        3); ``key`` feeds stochastic kinds (``needs_key``)."""
        raise NotImplementedError

    def decompress(self, message, like):
        """Dense pytree from a wire message (identity for dense wire)."""
        return message

    def ef_step(self, e, delta, key: Optional[jax.Array] = None):
        """Fused EF14 step: v = C(e + delta), e' = e + delta - v.

        Returns ``(message, e_new)`` where ``message`` is the wire
        representation of v (dense or payload, per backend)."""
        buf = _tree_add(e, delta)
        msg = self.compress(buf, key)
        e_new = _tree_sub(buf, self.decompress(msg, buf))
        return msg, e_new

    def wire_bytes(self, like) -> int:
        """Exact wire bytes of one message for a ``like``-shaped pytree,
        derived from the actual wire representation (payload shapes), not an
        analytic estimate.  Cached per (cfg, backend, leaf shapes/dtypes) --
        round_step calls this every round, also on the eager path."""
        sig = (self.cfg, self.backend, tuple(
            (tuple(l.shape), str(l.dtype))
            for l in jax.tree_util.tree_leaves(like)))
        hit = _WIRE_BYTES_CACHE.get(sig)
        if hit is None:
            if len(_WIRE_BYTES_CACHE) > 512:
                _WIRE_BYTES_CACHE.clear()
            hit = _WIRE_BYTES_CACHE[sig] = int(self._wire_bytes(like))
        return hit

    def _wire_bytes(self, like) -> int:
        raise NotImplementedError

    # -- round-level call sites ---------------------------------------------

    def encode(self, e, deltas, mask, like, key: Optional[jax.Array] = None):
        """Per-client EF14 encode, no aggregation: returns ``(msgs, e_new)``
        where ``msgs`` is the stacked *wire representation* of every
        client's message ([n, ...] leading axis on each payload leaf) and
        non-participants (mask == 0) keep their residual untouched.

        This is the buffer-facing half of :meth:`transmit`: the async
        engine parks rows of ``msgs`` in its staleness buffer (compressed
        bytes, not dense deltas) and aggregates with :meth:`reduce`."""
        from repro.sharding import partition
        msgs, e_stack = self._ef_clients(e, deltas, like, key)
        e_out = e
        if e is not None:
            e_stack = partition.constrain_leading(e_stack, "client")
            e_out = _mask_where(mask, e_stack, e)
        if self.wire == "dense":
            msgs = partition.constrain_leading(msgs, "client")
        return msgs, e_out

    def encode_gathered(self, e, deltas, idx, mask, like,
                        key: Optional[jax.Array] = None):
        """Compute-sparse variant of :meth:`encode` (engine.participation
        ``gather`` mode): ``deltas`` carries only the m participants' rows
        ([m, ...], sorted by client index ``idx``); ``e`` keeps the full
        [n, ...] layout.

        The EF14 step runs over m rows (per-client results identical to the
        mask path's, incl. per-client PRNG keys), residuals scatter back in
        place, and messages scatter into the full [n, ...] layout so
        downstream aggregation/buffering is the same op as the mask
        path's -- trajectories match bit-for-bit while EF compute and state
        traffic scale with m."""
        from repro.sharding import partition
        n = mask.shape[0]
        e_part = None if e is None else \
            tree_map(lambda x: take_rows(x, idx), e)
        keys = None
        if self.needs_key and key is not None:
            keys = jnp.take(jax.random.split(key, n), idx, axis=0)
        msgs, e_stack = self._ef_clients(e_part, deltas, like, key, keys=keys)
        e_out = e
        if e is not None:
            e_stack = partition.constrain_leading(e_stack, "client")
            e_out = tree_map(lambda E, En: put_rows(E, idx, En), e, e_stack)
        msgs = scatter_rows(msgs, idx, n)
        if self.wire == "dense":
            msgs = partition.constrain_leading(msgs, "client")
        return msgs, e_out

    def reduce(self, msgs, weights, m, like):
        """Weighted aggregation of stacked wire messages:
        ``sum_j weights_j * decompress(msgs_j) / m``.

        ``weights`` is any [n] array (a 0/1 mask, the sampler's HT weights,
        or the async engine's staleness-composed weights); zero rows
        contribute nothing, so garbage payloads in unoccupied buffer slots
        or unsampled mask rows are harmless."""
        if self.wire == "dense":
            return masked_mean(msgs, weights, m)
        return self._aggregate_packed(msgs, weights, m, like)

    def transmit(self, e, deltas, mask, m, like, key: Optional[jax.Array] = None):
        """Per-client EF14 + masked mean over the client axis
        (:meth:`encode` then :meth:`reduce`).

        ``e``/``deltas`` carry a leading [n_clients] axis; non-participants
        (mask == 0) keep their residual untouched.  Returns
        ``(v_bar, e_new)``."""
        msgs, e_out = self.encode(e, deltas, mask, like, key)
        return self.reduce(msgs, mask, m, like), e_out

    def transmit_gathered(self, e, deltas, idx, mask, m, like,
                          key: Optional[jax.Array] = None):
        """Compute-sparse variant of :meth:`transmit`
        (:meth:`encode_gathered` then :meth:`reduce`)."""
        msgs, e_out = self.encode_gathered(e, deltas, idx, mask, like, key)
        return self.reduce(msgs, mask, m, like), e_out

    def broadcast(self, w, x_new, key: Optional[jax.Array] = None):
        """Primal-EF21 downlink: w' = w + C(x_new - w)."""
        diff = _tree_sub(x_new, w)
        msg = self.compress(diff, key)
        return _tree_add(w, self.decompress(msg, w))

    # -- internals ----------------------------------------------------------

    def _ef_clients(self, e, deltas, like, key, keys=None):
        """EF14 over the stacked client axis (vmap by default).  ``keys``
        overrides the per-client PRNG keys (the gathered path passes the
        participants' rows of the mask path's ``split(key, n)``)."""
        n = _leading_dim(deltas)
        if self.needs_key and key is not None:
            if keys is None:
                keys = jax.random.split(key, n)
            return jax.vmap(self.ef_step)(e, deltas, keys)
        return jax.vmap(lambda ej, dj: self.ef_step(ej, dj))(e, deltas)

    def _aggregate_packed(self, msgs, mask, m, like):
        # Beyond-paper wire path (DESIGN.md §Transport / §Hotpath): the
        # cross-client aggregation consumes only the packed payload -- the
        # collective moves ~K/d of the model bytes -- and reduces in the
        # PAYLOAD domain, client-parallel: select payloads scatter-add their
        # (value, block-offset) streams into the dense accumulator in one
        # op; quant payloads contract codes*scale over the client axis
        # (fused unpack-multiply-add).  The former per-client lax.scan kept
        # O(1) dense buffers but made aggregation latency linear-sequential
        # in n; the parallel reduction's only cost is the transient
        # weighted-code tensor (same footprint as the delta stack).
        from repro.kernels import ops
        from repro.sharding import partition
        packed_repl = partition.gather_leading(msgs)
        n = mask.shape[0]

        def one(p, ref):
            shape = tuple(ref.shape) if ref.ndim else (1,)
            if isinstance(p, QuantPayload):
                levels = float(2 ** (self.cfg.bits - 1) - 1)
                wsum = jnp.tensordot(
                    mask.astype(jnp.float32),
                    p.codes.astype(jnp.float32) * p.scale, axes=(0, 0))
                return (wsum / levels).reshape(tuple(ref.shape)) \
                    .astype(ref.dtype)
            # select payloads land on the same tuned bucket-aggregation
            # entry point as FlatTransport.reduce: each of the L block
            # rows of width b is a destination bucket
            k = p.values.shape[-1]
            nb = p.values.shape[-2]
            b = shape[-1] // nb
            L = int(np.prod(p.values.shape[1:-1], dtype=np.int64))
            acc = ops.scatter_agg(p.values.reshape(n, L, k),
                                  p.indices.reshape(n, L, k),
                                  mask, block=b)
            return acc.reshape(tuple(ref.shape)).astype(ref.dtype)

        v_sum = tree_map(one, packed_repl, like,
                         is_leaf=payloads.is_payload)
        return tree_map(lambda v: v / m, v_sum)

    def _payload_wire_bytes(self, like) -> int:
        """Wire bytes from the payload shapes the packer would emit."""
        sds = tree_map(lambda l: jax.ShapeDtypeStruct(l.shape, l.dtype), like)
        shapes = jax.eval_shape(
            lambda t: self.compress(t, jax.random.PRNGKey(0)), sds)
        return payloads.payload_wire_bytes(
            shapes, self.cfg.bits if self.cfg.kind == "quant" else None)


# ---------------------------------------------------------------------------
# Kind registry entries
# ---------------------------------------------------------------------------

@register
class IdentityTransport(Transport):
    """kind='none': dense wire, no residual, no center tracking."""

    kind = "none"

    @property
    def is_identity(self) -> bool:
        return True

    def compress(self, tree, key=None):
        return tree

    def ef_step(self, e, delta, key=None):
        if e is None:
            return delta, None
        buf = _tree_add(e, delta)
        return buf, _tree_zeros_like(buf)

    def _wire_bytes(self, like) -> int:
        return int(sum(l.size * jnp.dtype(l.dtype).itemsize
                       for l in jax.tree_util.tree_leaves(like)))

    def encode(self, e, deltas, mask, like, key=None):
        return deltas, e

    def encode_gathered(self, e, deltas, idx, mask, like, key=None):
        return scatter_rows(deltas, idx, mask.shape[0]), e

    def transmit(self, e, deltas, mask, m, like, key=None):
        return masked_mean(deltas, mask, m), e

    def transmit_gathered(self, e, deltas, idx, mask, m, like, key=None):
        dense = scatter_rows(deltas, idx, mask.shape[0])
        return masked_mean(dense, mask, m), e

    def broadcast(self, w, x_new, key=None):
        return x_new


class _BlockSelectTransport(Transport):
    """Shared machinery for the (values, indices) payload kinds."""

    def decompress(self, message, like):
        if self.wire == "dense":
            return message
        return payloads.unpack_tree(message, like, self.cfg)

    def _wire_bytes(self, like) -> int:
        if self.wire != "dense":
            return self._payload_wire_bytes(like)
        # ref backend: global per-leaf selection of k = round(d * ratio)
        # entries, each one value (leaf dtype) + int32 index on the wire.
        # Giant leaves mirror compress_leaf's blockwise fallback (> 2^22
        # elements switch to block_topk_dense), so the measured count
        # follows the selection that actually runs.
        total = 0
        for l in jax.tree_util.tree_leaves(like):
            if l.size > payloads._SORT_FREE_MIN:
                D = l.shape[-1] if len(l.shape) else 1
                b, kb = block_geometry(D, self.cfg)
                k = (l.size // D) * (D // b) * kb
            else:
                k = max(1, int(round(l.size * self.cfg.ratio)))
            total += k * (jnp.dtype(l.dtype).itemsize + 4)
        return int(total)


@register
class TopKTransport(_BlockSelectTransport):
    """kind='topk': magnitude top-k.

    ref: global per-leaf argsort selection (giant leaves fall back to the
    blockwise threshold path); packed: blockwise (values, indices) payload;
    pallas: blockwise selection inside the ``topk_block`` kernel (an exact
    bit-pattern threshold and an order-keeping compaction over a
    VMEM-resident block), emitting the same payload in ascending offset."""

    kind = "topk"

    @property
    def wire(self) -> str:
        return "dense" if self.backend == "ref" else "packed"

    def compress(self, tree, key=None):
        if self.backend == "ref":
            from repro.core import compression
            return compression.compress(tree, self.cfg)
        if self.backend == "packed":
            return payloads.pack_tree(tree, self.cfg)
        return tree_map(lambda l: self._pack_leaf_kernel(l), tree)

    def _pack_leaf_kernel(self, x: jnp.ndarray) -> PackedLeaf:
        from repro.kernels.topk_block import block_topk
        if x.ndim == 0:
            x = x.reshape(1)
        D = x.shape[-1]
        b, k = block_geometry(D, self.cfg)
        blocks = x.reshape(x.shape[:-1] + (D // b, b))
        if k >= b:
            idx = jnp.broadcast_to(
                jnp.arange(b, dtype=payloads.INDEX_DTYPE), blocks.shape).copy()
            return PackedLeaf(blocks, idx)
        vals, idx = block_topk(blocks, k)
        return PackedLeaf(vals[..., :k],
                          idx[..., :k].astype(payloads.INDEX_DTYPE))

    def _ef_clients(self, e, deltas, like, key, keys=None):
        if self.backend != "pallas":
            return super()._ef_clients(e, deltas, like, key, keys=keys)
        # fold the client axis into the kernel grid: blocking runs along the
        # last tensor axis, so the stacked [n, ...] tree packs in ONE kernel
        # launch per leaf instead of a vmap over pallas_call
        buf = _tree_add(e, deltas)

        def pack_stacked(x, ref):
            x2 = x.reshape(x.shape + (1,)) if ref.ndim == 0 else x
            return self._pack_leaf_kernel(x2)

        msgs = tree_map(pack_stacked, buf, like)

        def unpack_stacked(p, x, ref):
            shape = x.shape + (1,) if ref.ndim == 0 else x.shape
            b = choose_block(shape[-1], self.cfg.block, self.cfg.shards)
            dense = payloads.block_topk_unpack(p, shape, x.dtype, block=b)
            return dense.reshape(x.shape)

        dense_v = tree_map(
            lambda p, x, ref: unpack_stacked(p, x, ref), msgs, buf, like,
            is_leaf=lambda nd: isinstance(nd, PackedLeaf))
        return msgs, _tree_sub(buf, dense_v)


@register
class RandKTransport(_BlockSelectTransport):
    """kind='randk': k uniformly random coordinates (no rescale).

    ref: global per-leaf sampling; packed/pallas: blockwise payload (no
    kernel exists -- pallas aliases the packed math)."""

    kind = "randk"
    needs_key = True

    @property
    def wire(self) -> str:
        return "dense" if self.backend == "ref" else "packed"

    def compress(self, tree, key=None):
        assert key is not None, "randk needs a PRNG key"
        if self.backend == "ref":
            from repro.core import compression
            return compression.compress(tree, self.cfg, key)
        leaves, treedef = jax.tree_util.tree_flatten(tree)
        keys = jax.random.split(key, len(leaves))
        out = [payloads.block_randk_pack(l, self.cfg, k)
               for l, k in zip(leaves, keys)]
        return jax.tree_util.tree_unflatten(treedef, out)


@register
class QuantTransport(Transport):
    """kind='quant': per-block max-abs symmetric b-bit rounding.

    ref: dense jnp quantizer; packed: (int8 codes, fp32 scales) payload
    crosses the client axis; pallas: the EF14 step runs fused in the
    ``quantize_ef`` kernel -- quantizer + residual update in one pass over
    the VMEM-resident block, saving a full HBM round-trip of the
    (e + delta) buffer per round.  The fused kernel emits dense v, so the
    pallas wire stays dense (compute fusion, not wire packing)."""

    kind = "quant"

    @property
    def wire(self) -> str:
        return "packed" if self.backend == "packed" else "dense"

    def compress(self, tree, key=None):
        if self.backend == "ref":
            from repro.core import compression
            return compression.compress(tree, self.cfg)
        if self.backend == "packed":
            return tree_map(lambda l: payloads.quant_pack(l, self.cfg), tree)
        # pallas: quantize via the fused kernel with a zero residual
        zeros = _tree_zeros_like(tree)
        v, _ = self._fused_ef(zeros, tree, like=tree)
        return v

    def decompress(self, message, like):
        if self.wire == "dense":
            return message
        return tree_map(
            lambda p, ref: payloads.quant_unpack(p, ref.shape, ref.dtype, self.cfg),
            message, like, is_leaf=lambda nd: isinstance(nd, QuantPayload))

    def ef_step(self, e, delta, key=None):
        if self.backend == "pallas":
            v, e_new = self._fused_ef(e, delta, like=e)
            return v, e_new
        return super().ef_step(e, delta, key)

    def _fused_ef(self, e, delta, like):
        """Route every leaf through the fused quantize_ef kernel.  ``like``
        supplies the true per-client rank so stacked [n, ...] trees fold the
        client axis into the kernel grid (blocks run along the LAST axis,
        which stacking leaves untouched)."""
        from repro.kernels.quantize_ef import quantize_ef

        def one(ej, dj, ref):
            if ref.ndim == 0:
                # scalar leaves are not quantized (matches the ref path)
                buf = ej + dj
                return buf, jnp.zeros_like(buf)
            D = ej.shape[-1]
            b = choose_block(D, self.cfg.block, self.cfg.shards)
            blocks = ej.shape[:-1] + (D // b, b)
            v, en = quantize_ef(ej.reshape(blocks), dj.reshape(blocks),
                                self.cfg.bits)
            return v.reshape(ej.shape), en.reshape(ej.shape)

        out = tree_map(one, e, delta, like)
        v = tree_map(lambda _, o: o[0], like, out)
        e_new = tree_map(lambda _, o: o[1], like, out)
        return v, e_new

    def _ef_clients(self, e, deltas, like, key, keys=None):
        if self.backend != "pallas":
            return super()._ef_clients(e, deltas, like, key, keys=keys)
        return self._fused_ef(e, deltas, like)

    def _wire_bytes(self, like) -> int:
        # format-based regardless of backend: ceil(bits/8 per code) packed
        # sub-byte on the wire + one fp32 scale per block
        total = 0.0
        for l in jax.tree_util.tree_leaves(like):
            D = l.shape[-1] if getattr(l, "ndim", len(l.shape)) else 1
            b = choose_block(D, self.cfg.block, self.cfg.shards)
            lead = l.size // D if D else 1
            total += l.size * self.cfg.bits / 8 + 4 * lead * (D // b)
        return int(total)


@register
class NaturalTransport(Transport):
    """kind='natural': stochastic power-of-two rounding (Horvath et al.).

    Dense wire on every backend (sign + 8-bit exponent stream; no payload
    materialization in the simulator)."""

    kind = "natural"
    needs_key = True

    def compress(self, tree, key=None):
        from repro.core import compression
        return compression.compress(tree, self.cfg, key)

    def _wire_bytes(self, like) -> int:
        d = sum(l.size for l in jax.tree_util.tree_leaves(like))
        return int(d * 9 / 8)
