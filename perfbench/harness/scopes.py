"""Attribute each device op of a reduced trace to the program's own scopes.

The program names its work with ``repro.obs.trace.stage`` scopes, which
land in each HLO instruction's ``op_name`` metadata (``harness/trace.py``
reads them into ``Op.meta``):

* ``model.embed`` / ``model.mixer`` / ``model.mlp`` / ``model.head`` /
  ``model.loss`` -- the model and its loss; an op under JAX's
  ``transpose(`` name stack is the backward pass (recomputed forward
  included), any other the forward,
* ``local.update`` -- the local steps' SGD updates,
* ``engine.delta_norm`` / ``engine.bookkeeping`` -- the round's diagnostic
  norm and its tail,
* ``flat.flatten`` / ``flat.unflatten`` -- the pytree <-> ``[d]`` copies,
* ``comm.rows`` / ``comm.relayout`` -- the wire's data movement outside
  its kernels, beside ``comm.*`` and ``kernel.*`` around the kernels,
* ``round.<stage>`` -- the round's stages, read by ``trace.stage_of``.

XLA makes some instructions without metadata (hyphenated names such as
``dynamic-update-slice.148``), most of them inside loop bodies: such an op
of the round program takes the scope path of the innermost op of its
device that encloses it in time (its loop; :func:`paths`).  Ops of other
programs carry the program's name.

:func:`part_of` puts each path in exactly one part, first match wins:

1. ``traffic``: the benchmark's batch generator,
2. ``model``: under a ``model.*`` scope, by its innermost one and by
   forward or backward,
3. ``delta_norm``: under ``engine.delta_norm``,
4. ``local_update``: under ``round.eval_round`` or ``round.local_deltas``
   and no model scope (the SGD updates, the gradient's assembly into
   ``[d]``, cotangents, the layer scans' own slicing and stacking),
5. ``wire_moves``: under ``comm.rows`` or ``comm.relayout``,
6. ``unscoped``: under no scope of the program,
7. ``wire_rest`` under ``round.encode_reduce`` / ``round.downlink``, else
   ``engine_rest``.

The parts' self times add up to the window's, so the new per-layer
metrics and the rest partition the busy round.  A program without these
scopes (no op under ``model.*``) gives no reading.
"""
from __future__ import annotations

import re
from collections import defaultdict
from typing import NamedTuple

from harness.trace import _STAGE, TRAFFIC

_MODEL = re.compile(r"model\.([A-Za-z_]+)")
_PROGRAM = re.compile(
    r"(?<![\w.])(?:round|model|local|engine|flat|comm|kernel)\.[A-Za-z_]")
LOCAL_STAGES = ("round.eval_round", "round.local_deltas")
WIRE_STAGES = ("round.encode_reduce", "round.downlink")
NEST_SLACK_NS = 1


class Part(NamedTuple):
    name: str               # one of the parts in the module docstring
    kind: str = ""          # model: the innermost model.<kind> scope
    backward: bool = False  # model: under transpose(


def paths(ops) -> list:
    """Each op's scope path: its own metadata, or, for an op of the round
    program with none, that of the innermost op of its device enclosing it
    in time ("" if none does).  The trace's nanosecond rounding can end a
    loop's last body op 1 ns after the loop (one op in several thousand on a
    v5e): an op ending up to :data:`NEST_SLACK_NS` after another still
    counts as enclosed in it."""
    out = [o.meta for o in ops]
    order = sorted(range(len(ops)),
                   key=lambda i: (ops[i].device, ops[i].start, -ops[i].end))
    stack: list = []
    for i in order:
        o = ops[i]
        while stack and (ops[stack[-1]].device != o.device
                         or ops[stack[-1]].end <= o.start
                         or ops[stack[-1]].end + NEST_SLACK_NS < o.end):
            stack.pop()
        if not out[i] and stack:
            out[i] = out[stack[-1]]
        stack.append(i)
    return out


def part_of(path: str) -> Part:
    """The one part a scope path belongs to (module docstring)."""
    if TRAFFIC in path:
        return Part("traffic")
    kinds = _MODEL.findall(path)
    if kinds:
        return Part("model", kinds[-1], "transpose(" in path)
    if "engine.delta_norm" in path:
        return Part("delta_norm")
    m = _STAGE.search(path)
    stage = m.group(0) if m else ""
    if stage in LOCAL_STAGES:
        return Part("local_update")
    if "comm.rows" in path or "comm.relayout" in path:
        return Part("wire_moves")
    if not _PROGRAM.search(path):
        return Part("unscoped")
    return Part("wire_rest" if stage in WIRE_STAGES else "engine_rest")


def split(summary) -> dict:
    """Device self ms per round by :class:`Part`, averaged over the devices,
    or None where no op carries a ``model.*`` scope (a program without
    this module's scopes).  Kept on the summary: every reader of one trace
    shares it."""
    if not hasattr(summary, "_scope_parts"):
        got = defaultdict(float)
        for path, t in zip(paths(summary.ops), summary.self_ns):
            got[part_of(path)] += t
        scale = 1e-6 / max(1, len(summary.devices)) / summary.rounds
        scoped = any(p.name == "model" for p in got)
        summary._scope_parts = {k: v * scale for k, v in got.items()} \
            if scoped else None
    return summary._scope_parts


def ms(ctx, keep) -> float | None:
    """Device ms per round of the parts ``keep(part)`` selects; None where
    the trace holds no op of a scoped program."""
    parts = split(ctx.trace)
    if parts is None:
        return None
    return sum(v for p, v in parts.items() if keep(p))


def unscoped_ops(summary, k: int = 10) -> list:
    """The ops under no scope of the program, with the most self time:
    ``[name (opcode), ms per round]``."""
    tot = defaultdict(int)
    for o, path, t in zip(summary.ops, paths(summary.ops), summary.self_ns):
        if part_of(path).name == "unscoped":
            tot[f"{o.name} ({o.opcode})"] += t
    scale = 1e-6 / max(1, len(summary.devices)) / summary.rounds
    best = sorted(tot.items(), key=lambda kv: -kv[1])[:k]
    return [[name, ns * scale] for name, ns in best]
