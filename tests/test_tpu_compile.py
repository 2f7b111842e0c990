"""Compile every Pallas kernel of the main path for a described TPU v5e.

No chip is needed: the TPU compiler is installed with jaxlib and compiles
for a topology that is described, not attached.  Each test lowers one
kernel with ``interpret=False`` at the widths of a full-size round
(``smollm-360m``: d ~ 362M, i.e. ~353k quantization blocks of 1024) and
checks that Mosaic accepts it and the program holds a ``tpu_custom_call``.
Nothing runs, so nothing here says anything about results or speed.

The topology is described inside a module-scoped fixture, never at import:
only one process may load the TPU library at a time, and every test worker
imports this file.
"""
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.comm.payloads import words_per_block
from repro.kernels.quantize_ef import quantize_ef
from repro.kernels.quantize_ef_pack import quantize_ef_pack
from repro.kernels.scatter_agg import scatter_agg, segment_rows
from repro.kernels.switch_blend import switch_blend
from repro.kernels.topk_block import block_topk
from repro.kernels.unpack_mma import unpack_mma

NBLOCKS = 353_792           # ~362M / 1024: one full-width smollm-360m buffer
CLIENTS = 2                 # encode kernels see [clients, nblocks, block]
# the block widths a smollm-360m wire layout runs at (d_model 960, kv 320,
# d_ff 2560 -> 640) next to the aligned default
BLOCKS = (1024, 960, 640, 320)


@pytest.fixture(scope="module")
def chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 -- any failure: cannot describe
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip cannot be read back from the
    # persistent cache without the chip: keep the cache out of it
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", prev)


def _compile(chip, fn, *shapes):
    args = [jax.ShapeDtypeStruct(s, d, sharding=chip) for s, d in shapes]
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


@pytest.mark.parametrize("block", BLOCKS)
@pytest.mark.parametrize("bits", [4, 8])
def test_quantize_ef_pack(chip, bits, block):
    x = ((CLIENTS, NBLOCKS, block), jnp.float32)
    _compile(chip, lambda e, d: quantize_ef_pack(e, d, bits, interpret=False),
             x, x)


@pytest.mark.parametrize("block", BLOCKS)
def test_quantize_ef(chip, block):
    # the tree path quantizes leaf by leaf: one full-width buffer's worth
    # of blocks (two clients of v and e_new would not fit the chip)
    x = ((NBLOCKS, block), jnp.float32)
    _compile(chip, lambda e, d: quantize_ef(e, d, 8, interpret=False), x, x)


@pytest.mark.parametrize("block", BLOCKS)
def test_block_topk(chip, block):
    k = round(block * 0.1)
    _compile(chip, lambda x: block_topk(x, k, interpret=False),
             ((CLIENTS, NBLOCKS, block), jnp.float32))


@pytest.mark.parametrize("block", BLOCKS)
@pytest.mark.parametrize("bits", [4, 8])
def test_unpack_mma(chip, bits, block):
    n, W = 8, words_per_block(block, bits)
    _compile(chip, lambda w, s, wt: unpack_mma(w, s, wt, bits, block,
                                               interpret=False),
             ((n, NBLOCKS, W), jnp.uint32), ((n, NBLOCKS), jnp.float32),
             ((n,), jnp.float32))


@pytest.mark.parametrize("block", BLOCKS)
def test_scatter_agg(chip, block):
    n, k = 8, round(block * 0.1)
    _compile(chip, lambda v, i, w: scatter_agg(v, i, w, block,
                                               interpret=False),
             ((n, NBLOCKS, k), jnp.float32), ((n, NBLOCKS, k), jnp.uint16),
             ((n,), jnp.float32))


@pytest.mark.parametrize("m,n", [(2, 2), (8, 64)])
def test_segment_rows(chip, m, n):
    _compile(chip, lambda r, s: segment_rows(r, s, n, interpret=False),
             ((m, 1 << 20), jnp.float32), ((m,), jnp.int32))


def test_switch_blend(chip):
    g = ((1 << 20,), jnp.float32)
    _compile(chip, lambda a, b, s: switch_blend(a, b, s, interpret=False),
             g, g, ((), jnp.float32))


SMALL = 64                  # blocks: enough to lower, fast to compile
KERNELS = {
    "quantize_ef_pack": (lambda e, d: quantize_ef_pack(e, d, 8,
                                                       interpret=False),
                         ((2, SMALL, 1024), jnp.float32),
                         ((2, SMALL, 1024), jnp.float32)),
    "quantize_ef": (lambda e, d: quantize_ef(e, d, 8, interpret=False),
                    ((SMALL, 1024), jnp.float32),
                    ((SMALL, 1024), jnp.float32)),
    "unpack_mma": (lambda w, s, wt: unpack_mma(w, s, wt, 8, 1024,
                                               interpret=False),
                   ((2, SMALL, 256), jnp.uint32), ((2, SMALL), jnp.float32),
                   ((2,), jnp.float32)),
    "block_topk": (lambda x: block_topk(x, 102, interpret=False),
                   ((2, SMALL, 1024), jnp.float32)),
    "scatter_agg": (lambda v, i, w: scatter_agg(v, i, w, 1024,
                                                interpret=False),
                    ((2, SMALL, 102), jnp.float32),
                    ((2, SMALL, 102), jnp.uint16), ((2,), jnp.float32)),
    "segment_rows": (lambda r, s: segment_rows(r, s, 2, interpret=False),
                     ((2, 1 << 16), jnp.float32), ((2,), jnp.int32)),
    "switch_blend": (lambda a, b, s: switch_blend(a, b, s, interpret=False),
                     ((1 << 16,), jnp.float32), ((1 << 16,), jnp.float32),
                     ((), jnp.float32)),
}


@pytest.mark.parametrize("name", sorted(KERNELS))
def test_custom_call_named_after_kernel(chip, name):
    """Each kernel's launch is the custom call ``<kernel>`` or
    ``<kernel>.<n>`` (its ``pallas_call`` ``name=``), also under a caller's
    scope: the name the benchmark's roofline readers match."""
    fn, *shapes = KERNELS[name]

    def caller(*args):
        with jax.named_scope("round.encode_reduce"):
            return fn(*args)
    text = _compile(chip, caller, *shapes).as_text()
    calls = re.findall(r"%([\w.\-]+) = [^=]* custom-call\(", text)
    assert calls and all(re.fullmatch(re.escape(name) + r"(\.\d+)?", c)
                         for c in calls), calls
