"""Roofline-term derivation from compiled dry-run artifacts (TPU v5e model).

  compute term    = HLO_FLOPs / (chips * peak FLOP/s)
  memory term     = HLO_bytes / (chips * HBM bandwidth)
  collective term = collective_bytes / (chips * ICI link bandwidth)

with the peaks of the chip looked up by its ``device_kind`` (PEAKS).

collective_bytes is parsed from the compiled HLO text: we sum the operand
sizes of every all-gather / all-reduce / reduce-scatter / all-to-all /
collective-permute op (per the brief's prescription).
"""
from __future__ import annotations

import re
from typing import Dict

# Per-chip peaks keyed by ``jax.Device.device_kind``.  A device missing
# here is an error, never a default.
#   "TPU v5 lite" (TPU v5e) -- Google Cloud documentation, "TPU v5e":
#   197 TFLOP/s bf16, 16 GB HBM at 819 GB/s, 1,600 Gbit/s of inter-chip
#   interconnect (4 links, so 50 GB/s each).
PEAKS = {
    "TPU v5 lite": {"flops": 197e12, "hbm_bw": 819e9, "link_bw": 50e9},
}
# the chip the compile-only dry runs (launch/dryrun.py) model
DRYRUN_DEVICE_KIND = "TPU v5 lite"


def peaks(device_kind: str) -> Dict[str, float]:
    """Published peaks of one chip of ``device_kind``."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no published peaks for device kind "
                       f"{device_kind!r}; known: {sorted(PEAKS)}") from None

_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "f8e4m3fn": 1, "f8e5m2": 1,
    "s16": 2, "u16": 2, "bf16": 2, "f16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8,
    "c64": 8, "c128": 16,
}

_COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
                "collective-permute")

_SHAPE_RE = re.compile(r"(\w+)\[([\d,]*)\]")


def _shape_bytes(dtype: str, dims: str) -> int:
    n = 1
    for d in dims.split(","):
        if d:
            n *= int(d)
    return n * _DTYPE_BYTES.get(dtype, 4)


def collective_bytes(hlo_text: str) -> Dict[str, int]:
    """Sum operand bytes per collective kind from HLO text.

    Collectives inside while-loop bodies (lax.scan layers) execute
    trip-count times but appear once in the text; they are tallied
    separately under ``in_loop`` so the caller can apply a trip-count
    correction (dryrun passes the layer count).
    """
    out = {k: 0 for k in _COLLECTIVES}
    out["count"] = 0
    out["in_loop"] = 0
    in_loop_comp = False
    for line in hlo_text.splitlines():
        stripped = line.strip()
        comp = re.match(r"%?([\w\.\-]+)\s*(?:\([^)]*\))?\s*->.*{\s*$", stripped)
        if comp or stripped.startswith("ENTRY"):
            name = comp.group(1) if comp else "entry"
            in_loop_comp = any(t in name for t in
                               ("while", "body", "scan", "cond"))
        m = re.match(r"%?[\w\.\-]+\s*=\s*(.*)$", stripped)
        if not m:
            continue
        rhs = m.group(1)
        for kind in _COLLECTIVES:
            # op name appears right before the '(' of its operand list
            mm = re.search(r"(?:^|\s)" + kind + r"(?:-start|-done)?\(", rhs)
            if not mm:
                continue
            if kind + "-done" in rhs:
                break  # counted at -start
            # operand shapes appear inline: op(bf16[8,16]{1,0} %x, ...)
            operands = rhs[mm.end():]
            depth = 1
            end = 0
            for i, ch in enumerate(operands):
                if ch == "(":
                    depth += 1
                elif ch == ")":
                    depth -= 1
                    if depth == 0:
                        end = i
                        break
            args = operands[:end] if end else operands
            total = sum(_shape_bytes(dt, dims)
                        for dt, dims in _SHAPE_RE.findall(args))
            if total == 0:
                # fallback: use the result shape on the lhs
                ms = _SHAPE_RE.search(rhs)
                if ms:
                    total = _shape_bytes(ms.group(1), ms.group(2))
            out[kind] += total
            out["count"] += 1
            if in_loop_comp:
                out["in_loop"] += total
            break
    out["total"] = sum(out[k] for k in _COLLECTIVES)
    return out


def corrected_collective_bytes(coll: Dict[str, int], trips: int) -> int:
    """total with loop-body collectives multiplied by the scan trip count."""
    outside = coll["total"] - coll.get("in_loop", 0)
    return int(outside + coll.get("in_loop", 0) * max(trips, 1))


def cost_summary(compiled) -> Dict[str, float]:
    ca = compiled.cost_analysis()
    if isinstance(ca, list):
        ca = ca[0]
    flops = float(ca.get("flops", 0.0))
    byts = float(ca.get("bytes accessed", 0.0))
    return {"flops": flops, "bytes": byts}


def memory_summary(compiled) -> Dict[str, float]:
    ma = compiled.memory_analysis()
    out = {}
    for k in ("argument_size_in_bytes", "output_size_in_bytes",
              "temp_size_in_bytes", "generated_code_size_in_bytes",
              "alias_size_in_bytes"):
        out[k] = float(getattr(ma, k, 0) or 0)
    out["total_per_device"] = (out.get("argument_size_in_bytes", 0)
                               + out.get("temp_size_in_bytes", 0)
                               + out.get("output_size_in_bytes", 0)
                               - out.get("alias_size_in_bytes", 0))
    return out


def roofline_terms(flops: float, hbm_bytes: float, coll_bytes: float,
                   chips: int, device_kind: str) -> Dict[str, float]:
    """All three terms in seconds + the dominant bottleneck.

    NOTE: cost_analysis() and as_text() describe the SPMD-*partitioned*
    module, i.e. the per-device program (verified empirically: per-device
    flops ~= MODEL_FLOPS/chips for dense archs).  The brief's
    "/(chips * peak)" normalization applies to whole-mesh totals; with
    per-device numbers the chips factor is already folded in.
    """
    peak = peaks(device_kind)
    t_compute = flops / peak["flops"]
    t_memory = hbm_bytes / peak["hbm_bw"]
    t_coll = coll_bytes / peak["link_bw"]
    dom = max(("compute", t_compute), ("memory", t_memory),
              ("collective", t_coll), key=lambda kv: kv[1])[0]
    return {"compute_s": t_compute, "memory_s": t_memory,
            "collective_s": t_coll, "dominant": dom}


def model_flops(cfg, n_tokens: int) -> float:
    """MODEL_FLOPS = 6 N D (dense) / 6 N_active D (MoE); train fwd+bwd."""
    return 6.0 * cfg.n_active_params() * n_tokens


def model_flops_forward(cfg, n_tokens: int) -> float:
    return 2.0 * cfg.n_active_params() * n_tokens
