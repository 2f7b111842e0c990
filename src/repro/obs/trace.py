"""Stage-level tracing (DESIGN.md §Obs).

:func:`stage` is the one scope primitive used across the codebase: a
``jax.named_scope`` that names the emitted HLO ops, so each device op of a
profile carries the scopes of the code that made it in its ``op_name``
metadata.  Scopes are metadata only -- wrapping code in a stage changes
no numerics and no compiled program (verified by the obs tests), which is
why the code wraps its stages unconditionally.  The scopes:

* ``round.<stage>`` -- the round engine's stages (``engine/rounds.py``,
  ``engine/async_rounds.py``),
* ``model.embed`` / ``model.mixer`` / ``model.mlp`` / ``model.head`` --
  the model's token embedding, its attention or SSD mixer blocks, its MLP
  blocks, its final norm and logits; ``model.loss`` the LM task's losses,
* ``local.update`` -- the local steps' SGD updates on the flat buffer,
* ``engine.delta_norm`` / ``engine.bookkeeping`` -- the round's diagnostic
  norm and its tail (averaged iterate, the next state's views),
* ``flat.flatten`` / ``flat.unflatten`` -- the pytree <-> ``[d]`` copies,
* ``comm.*`` -- the flat transport (``comm.ef_encode``, ``comm.reduce``,
  ``comm.broadcast``) and its data movement (``comm.rows``,
  ``comm.relayout``); ``kernel.*`` the Pallas call sites.

:class:`ProfileWindow` backs the launcher's ``--profile start:stop`` flag:
it starts ``jax.profiler.start_trace`` when the round counter enters the
window and writes a Perfetto-viewable trace directory when it leaves
(view at https://ui.perfetto.dev or ``tensorboard --logdir <dir>``).
"""
from __future__ import annotations

import base64
import hashlib
import re

import jax


def stage(name: str):
    """A named scope, ``jax.named_scope(name)``, on the ops traced inside
    it.  Metadata only -- numerics are untouched."""
    return jax.named_scope(name)


_METADATA = re.compile(r', metadata=\{(?:[^}"]|"(?:[^"\\]|\\.)*")*\}')
_DEBUG_TABLE = re.compile(
    r"^(?:FileNames|FunctionNames|FileLocations|StackFrames)\n(?:.+\n)*",
    re.M)
_KERNEL_BODY = re.compile(r'"body":"([A-Za-z0-9+/=]+)"')
_MODULE_NAME = re.compile(r"^module @[\w.$-]+", re.M)
_NAME = re.compile(r"%([\w.\-]+)")


def _kernel_digest(m: re.Match) -> str:
    """A Pallas TPU kernel's serialized Mosaic module, as a digest of its
    text without source locations and without the module's name (the
    kernel's ``name=``)."""
    from jaxlib.mlir import ir
    ctx = ir.Context()
    ctx.allow_unregistered_dialects = True
    with ctx:
        text = str(ir.Module.parse(base64.b64decode(m.group(1))))
    text = _MODULE_NAME.sub("module", text)
    return '"body":"sha256:%s"' % hashlib.sha256(text.encode()).hexdigest()


def strip_metadata(hlo_text: str) -> str:
    """A compiled program's HLO text (``compiled.as_text()``) without what
    names and locates its ops: every ``metadata={...}`` (the ``op_name``
    that :func:`stage` scopes land in), the source-location tables, the
    source locations and names inside Pallas TPU kernels, and the
    instructions' own names, which become their order of first appearance
    (XLA numbers the instructions its passes make from counters that the
    scopes shift).  Two programs that differ only in their scopes strip to
    the same text."""
    text = _DEBUG_TABLE.sub("", _METADATA.sub("", hlo_text))
    text = _KERNEL_BODY.sub(_kernel_digest, text)
    names: dict = {}
    return _NAME.sub(lambda m: "%%%d" % names.setdefault(m.group(1),
                                                         len(names)), text)


class ProfileWindow:
    """Capture a profiler trace for a window of rounds.

    ``spec`` is ``"start:stop"`` in round numbers (capture while
    ``start <= round < stop``), e.g. ``--profile 10:20``; ``""``/None
    disables (every call is a no-op).  Drive the window from the training
    loop with :meth:`tick` -- idempotent per state, so chunked drivers may
    call it at chunk granularity::

        >>> win = ProfileWindow("10:20", out_dir="profiles")
        >>> for chunk in range(...):
        ...     win.tick(done_rounds)      # starts/stops as the window
        ...     state, hist = drive(...)   # boundary is crossed
        >>> win.close()                    # stop if still capturing
    """

    def __init__(self, spec: str | None, out_dir: str = "profiles"):
        self.out_dir = out_dir
        self.active = False
        self.done = False
        if not spec:
            self.start = self.stop = None
            self.done = True
            return
        try:
            a, b = spec.split(":")
            self.start, self.stop = int(a), int(b)
        except ValueError:
            raise ValueError(
                f"--profile expects 'start:stop' round numbers, got {spec!r}")
        if self.stop <= self.start:
            raise ValueError(
                f"--profile window is empty: {self.start}:{self.stop}")

    def tick(self, rnd: int) -> None:
        """Advance to round ``rnd``: start capturing when the window opens,
        write the trace when it closes."""
        if self.done:
            return
        if not self.active and self.start <= rnd < self.stop:
            jax.profiler.start_trace(self.out_dir)
            self.active = True
        elif self.active and rnd >= self.stop:
            jax.profiler.stop_trace()
            self.active = False
            self.done = True

    def close(self) -> None:
        """Stop a still-open capture (end of run inside the window)."""
        if self.active:
            jax.profiler.stop_trace()
            self.active = False
            self.done = True
