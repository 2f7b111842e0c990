"""The federation round engine: everything between "here is a FedState" and
"here is the next one".

One :func:`round_step` implements a full communication round for any
registered strategy (engine.strategies):

  1. sample S_t (the ``cfg.fleet.sampler`` law from repro.fleet.samplers --
     uniform / weighted / markov -- executed dense-mask or compute-sparse
     gather per engine.participation); with a :class:`repro.fleet.Fleet` as
     ``batches``, provision this round's per-client minibatches in-jit
     (fleet.provision.minibatch, per-client ``fold_in`` streams),
  2. constraint query: G_hat(w_t) over the participants (and, unless
     ``cfg.full_eval`` is off, the all-client g_full eval metric),
  3. strategy switch weight sigma_t,
  4. E local steps per client on the strategy's local objective,
  5. uplink EF14 compression of Delta_j = (w_t - w_{j,E}) / eta through the
     transport layer (repro.comm),
  6. strategy server update x_{t+1},
  7. downlink primal-EF21 broadcast w_{t+1} = w_t + C_0(x_{t+1} - w_t).

Compressor/wire/backend dispatch lives in repro.comm; participation-mode
dispatch lives in engine.participation; the strategy supplies only the
round's math.  :func:`drive` is the fully-jitted multi-round driver
(donated-buffer lax.scan, metric offload per chunk, host-callback progress
hook); :func:`run_rounds` / :func:`run_rounds_scan` keep the seed
signatures as shims.
"""
from __future__ import annotations

from typing import Callable, NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro import comm
from repro.comm import flat
from repro.comm.flat import flat_transports_for
from repro.configs.base import FedConfig
from repro.core.compression import message_bytes
from repro.engine import participation, strategies
from repro.fleet import provision, samplers
from repro.obs import bus as obs_bus
from repro.obs import trace as obs_trace
from repro.optim.sgd import tree_axpy, tree_zeros_like
from repro.sharding import partition

tree_map = jax.tree_util.tree_map


class FedState(NamedTuple):
    w: object               # broadcast model w_t (all clients hold this)
    x: object               # server center x_t (== w when downlink uncompressed)
    e_up: object            # uplink EF residuals, leading axis [n_clients]
    wbar_sum: object        # running weighted sum of w_t over feasible rounds
    wbar_weight: jnp.ndarray
    t: jnp.ndarray
    key: jax.Array
    sampler: object = None  # client-sampler state (fleet.samplers; None for
                            # the stateless laws -- no extra pytree leaves)


class RoundMetrics(NamedTuple):
    f: jnp.ndarray          # mean client objective at w_t (participating)
    g_hat: jnp.ndarray      # aggregated constraint estimate (participating)
    g_full: jnp.ndarray     # constraint over all clients (eval only; the
                            # participating estimate when full_eval is off)
    sigma: jnp.ndarray      # switching weight used
    feasible: jnp.ndarray   # 1{G_hat <= eps}
    delta_norm: jnp.ndarray
    # measured wire bytes of this round's messages, from the transport's
    # actual wire representation (per participating client uplink / one
    # broadcast downlink) -- not the analytic message_bytes estimate
    up_bytes: jnp.ndarray
    down_bytes: jnp.ndarray
    f_full: jnp.ndarray     # mean objective over all clients (eval only)
    # the in-jit telemetry record (repro.obs.bus.Telemetry) when
    # cfg.obs.enabled; None otherwise -- an EMPTY pytree subtree, so the
    # disabled round's scan ys/carry gain no leaves and the compiled
    # engine is bit-for-bit the pre-obs one (the lean_metrics contract)
    telemetry: object = None


def transports_for(cfg: FedConfig):
    """(uplink, downlink) transports for a federation config."""
    backend = comm.backend_for(cfg.comm)
    return (comm.get_transport(cfg.uplink, backend),
            comm.get_transport(cfg.downlink, backend))


def init_state(params, cfg: FedConfig, key: Optional[jax.Array] = None) -> FedState:
    if key is None:
        key = jax.random.PRNGKey(cfg.seed)
    # Memory-scaled state (DESIGN.md §3): the uplink EF residual exists only
    # under uplink compression; the server center x is stored separately only
    # under downlink compression (otherwise x == w identically); the averaged
    # iterate accumulator is optional (theory tasks, not LM dry-runs).
    uplink, downlink = transports_for(cfg)
    e_up = None
    if uplink.needs_residual:
        spec = flat.spec_of(params)
        if cfg.scale.ef_slots:
            # population scale-out (repro.scale, DESIGN.md §Scale): a
            # capacity-bounded [cap, d] slot pool replaces the dense
            # residual -- EF memory scales with cap (>= m), not n
            from repro.scale import slots as slot_store
            slot_store.validate(cfg)
            e_up = slot_store.init(cfg.n_clients, cfg.scale.ef_slots,
                                   spec.d, spec.dtype)
        else:
            # the flat hot path (comm.flat): ONE [n, d] buffer instead of n
            # stacked pytrees -- every EF elementwise op is a single kernel
            e_up = jnp.zeros((cfg.n_clients, spec.d), spec.dtype)
    # x gets buffers of its own: round_program donates the state, and a
    # buffer cannot be donated twice (as w and as x)
    x = tree_map(jnp.copy, params) if downlink.tracks_center else None
    samp = samplers.get_sampler(cfg.fleet.sampler)
    return FedState(
        w=params, x=x, e_up=e_up,
        wbar_sum=tree_zeros_like(params) if cfg.track_wbar else None,
        wbar_weight=jnp.zeros(()),
        t=jnp.zeros((), jnp.int32),
        key=key,
        sampler=samp.init(cfg, jax.random.fold_in(key, 0x736D70)))  # "smp"


def averaged_iterate(state: FedState):
    """w_bar: the theorem's averaged iterate over feasible rounds."""
    if state.wbar_sum is None:
        return state.w
    wgt = jnp.maximum(state.wbar_weight, 1e-12)
    has = state.wbar_weight > 0
    return tree_map(
        lambda s, w: jnp.where(has, s / wgt, w), state.wbar_sum, state.w)


def sample_round(state: FedState, batches, key: jax.Array, cfg: FedConfig):
    """Stage 1: draw S_t via the configured sampler law.  Returns
    ``(part, samp_state, fleet-or-None)``."""
    fleet = batches if isinstance(batches, provision.Fleet) else None
    samp = samplers.get_sampler(cfg.fleet.sampler)
    mask, weights, samp_state = samp.sample(key, cfg, fleet=fleet,
                                            state=state.sampler)
    return participation.finalize(mask, weights, cfg), samp_state, fleet


def _eval_aggregates(part, f_ev, g_ev, sparse_eval: bool, m: int):
    """Participating/full scalar aggregates of the per-client (f, g) eval."""
    w_agg = participation.agg_weights(part)
    if sparse_eval:
        w_part = jnp.take(w_agg, part.idx)
        g_hat = jnp.sum(w_part * g_ev) / m
        f_part = jnp.sum(w_part * f_ev) / m
    else:
        g_hat = jnp.sum(w_agg * g_ev) / m
        f_part = jnp.sum(w_agg * f_ev) / m
    return f_part, g_hat, jnp.mean(g_ev), jnp.mean(f_ev)


def eval_clients(w, batches, loss_pair: Callable, cfg: FedConfig):
    """Stage 2's per-client eval forward: ``(f_j, g_j) = loss_pair(w, b_j)``
    vmapped over the stacked batch rows (chunked by ``cfg.client_chunk``).

    Rows are independent (the vmap carries no cross-row reductions), so any
    client subset computes bit-identical per-row values -- the property the
    gather-vs-mask parity oracle pins, and the reason a `repro.wire` worker
    holding only its own clients' rows reproduces the single-process eval
    exactly.

    The stage is sandwiched between ``optimization_barrier``s: embedded in
    a larger program (the scanned round body), XLA would otherwise fuse
    surrounding ops into the loss forward and reassociate its per-row
    reductions -- last-ulp row values that NO standalone program can
    reproduce, breaking the cross-process parity above.  The barriers pin
    the stage to compile exactly as it does alone; they only cost the
    (tiny) eval<->aggregate fusion in the unfused round path."""
    w, batches = jax.lax.optimization_barrier((w, batches))
    f_ev, g_ev = participation.client_vmap(
        lambda b: loss_pair(w, b), cfg.client_chunk)(batches)
    return jax.lax.optimization_barrier((f_ev, g_ev))


def _sgd_scan(w0, batch, grad_fn, eta, steps: int):
    """``steps`` local SGD steps on the flat buffer (one client's batch)."""
    def body(w, _):
        grad = grad_fn(w, batch)
        with obs_trace.stage("local.update"):
            return w - eta * grad, None
    w_E, _ = jax.lax.scan(body, w0, None, length=steps)
    return w_E


def local_deltas(wf, spec, strat, sigma, local_b, loss_pair: Callable,
                 cfg: FedConfig):
    """Stage 4's E local steps on the strategy objective, per client row:
    ``Delta_j = (wf - w_{j,E}) / eta`` over the stacked ``local_b`` rows.

    Shared verbatim between :func:`compute_round`'s unfused path and the
    `repro.wire` worker loop -- one copy of the math, so cross-process
    parity cannot drift from the single-process oracle."""
    E, eta = cfg.local_steps, cfg.lr
    obj = strat.local_objective(loss_pair, sigma, cfg)
    grad_fn = jax.grad(
        lambda wfj, batch: obj(flat.unflatten(spec, wfj), batch))

    def delta(b):
        w_E = _sgd_scan(wf, b, grad_fn, eta, E)
        with obs_trace.stage("local.update"):
            return (wf - w_E) / eta
    return participation.client_vmap(delta, cfg.client_chunk)(local_b)


def compute_round(state: FedState, wf, spec, batches, fleet, part, strat,
                  loss_pair: Callable, cfg: FedConfig):
    """Stages 2-4 on the flat buffer: in-jit fleet provisioning, the
    constraint query, the switch weight, and the E local steps -- the deltas
    come back as a single [m|n, d] stack (``comm.flat``), so every
    elementwise update is one fused op instead of a per-leaf kernel soup.

    Returns ``(batches, pre_gathered, f_part, g_hat, g_full, f_full, sigma,
    deltas)``.

    When ``cfg.full_eval`` is off, the eval forward and the first local step
    run over the SAME per-client rows -- so both fuse into one
    ``jax.vjp`` call: the forward delivers (f_ev, g_ev), the switch weight
    is computed from the aggregated values, and the pullback (with the
    strategy's objective cotangents at those values) delivers every
    client's step-1 gradient without re-running the forward.  One fewer
    full forward per round; per-client values/grads are bit-for-bit the
    unfused path's (tests/test_hotpath.py)."""
    m = cfg.m
    E, eta = cfg.local_steps, cfg.lr
    # -- in-jit batch provisioning (fleet only) -----------------------------
    # Gather mode without the full-n eval provisions only the m sampled
    # clients' minibatches, so provisioning FLOPs/memory scale with m.
    sparse_eval = part.idx is not None and not cfg.full_eval
    pre_gathered = False
    if fleet is not None:
        k_prov = provision.round_key(state.key, cfg)
        prov_idx = part.idx if sparse_eval else None
        batches = provision.minibatch(fleet, k_prov, cfg, idx=prov_idx)
        pre_gathered = prov_idx is not None

    # -- fused path: eval forward IS the step-1 forward ---------------------
    # Only when the eval rows coincide with the local-step rows -- full_eval
    # off (rows = the m sampled clients), or full-participation mask mode
    # where the local steps already run over all n rows so the full-n eval
    # coincides too -- and the strategy's objective factors through the
    # (f, g) pair (the base-class local_objective -- a strategy overriding
    # it opts out).  Partial-participation mask mode stays unfused even
    # though its local rows also span n: the fused batched forward differs
    # from the shared-W eval forward by an ulp, and the mask-vs-gather
    # bit-parity oracle (tests/test_engine.py) must keep comparing
    # identical eval programs at m < n.
    fused = ((not cfg.full_eval
              or (part.idx is None and cfg.m >= cfg.n_clients)) and
             type(strat).local_objective is strategies.Strategy.local_objective)
    if fused:
        local_b = batches if pre_gathered else participation.gather(
            part, batches)
        mb = jax.tree_util.tree_leaves(local_b)[0].shape[0]
        W0 = jnp.broadcast_to(wf, (mb, wf.shape[0]))
        fwd = participation.client_vmap(
            lambda wfj, b: loss_pair(flat.unflatten(spec, wfj), b),
            cfg.client_chunk)
        with obs_trace.stage("round.eval_round"):
            (f_ev, g_ev), pull = jax.vjp(lambda W: fwd(W, local_b), W0)
            f_part, g_hat, g_full, f_full = _eval_aggregates(
                part, f_ev, g_ev, sparse_eval, m)
        sigma = strat.switch_weight(g_hat, cfg)
        with obs_trace.stage("round.local_deltas"):
            cots = jax.vmap(jax.grad(
                lambda fg: strat.blend_values(fg[0], fg[1], sigma, cfg)))
            df, dg = cots((f_ev, g_ev))
            (dW,) = pull((df, dg))
            with obs_trace.stage("local.update"):
                W_E = W0 - eta * dW
            if E > 1:
                obj = strat.local_objective(loss_pair, sigma, cfg)
                grad_fn = jax.grad(
                    lambda wfj, batch: obj(flat.unflatten(spec, wfj), batch))
                W_E = participation.client_vmap(
                    lambda w1, b: _sgd_scan(w1, b, grad_fn, eta, E - 1),
                    cfg.client_chunk)(W_E, local_b)
            with obs_trace.stage("local.update"):
                deltas = (wf - W_E) / eta
        deltas = partition.constrain_flat(
            partition.constrain_leading(deltas, "client"))
        return (batches, pre_gathered, f_part, g_hat, g_full, f_full,
                sigma, deltas)

    # -- unfused: separate eval forward (paper-faithful default) ------------
    eval_b = participation.gather(part, batches) \
        if (sparse_eval and not pre_gathered) else batches
    with obs_trace.stage("round.eval_round"):
        f_ev, g_ev = eval_clients(state.w, eval_b, loss_pair, cfg)
        f_part, g_hat, g_full, f_full = _eval_aggregates(
            part, f_ev, g_ev, sparse_eval, m)
    sigma = strat.switch_weight(g_hat, cfg)

    local_b = batches if pre_gathered else \
        participation.gather(part, batches)             # [m|n, ...]
    with obs_trace.stage("round.local_deltas"):
        deltas = local_deltas(wf, spec, strat, sigma, local_b,
                              loss_pair, cfg)
    deltas = partition.constrain_flat(
        partition.constrain_leading(deltas, "client"))
    return (batches, pre_gathered, f_part, g_hat, g_full, f_full,
            sigma, deltas)


def finish_round(state: FedState, strat, cfg: FedConfig, spec, wf, part,
                 deltas, v_bar, e_up, uplink, downlink, samp_state, key,
                 k_down, f_part, g_hat, g_full, f_full, sigma,
                 slot_stats=None) -> tuple[FedState, RoundMetrics]:
    """Stages 6-7 + bookkeeping, shared with the asynchronous round: server
    update on the aggregated direction, primal-EF21 downlink broadcast,
    averaged-iterate accounting (Theorems 1/2), metrics, next FedState.

    Everything runs on the flat [d] buffers (``wf``/``v_bar``/``deltas``
    from :mod:`repro.comm.flat`); the next FedState's pytrees are views
    (unflatten) of the single updated buffer.  ``slot_stats`` carries the
    slot store's per-round telemetry counters from the uplink call site
    (None on the dense residual) into the obs bus."""
    with obs_trace.stage("round.server_update"):
        xf = flat.flatten(spec, state.x) if state.x is not None else wf
        x_new = strat.server_update(xf, v_bar, cfg, spec=spec)
    with obs_trace.stage("round.downlink"):
        w_new_f = downlink.broadcast(wf, x_new, key=k_down)
    with obs_trace.stage("engine.bookkeeping"):
        w_new = flat.unflatten(spec, partition.constrain_flat(w_new_f))
        x_keep = flat.unflatten(spec, x_new) if downlink.tracks_center \
            else None

        alpha = strat.iterate_weight(g_hat, cfg)
        wbar_sum = (tree_axpy(alpha, state.w, state.wbar_sum)
                    if state.wbar_sum is not None else None)

    # delta_norm pays a full extra [n, d] reduction: gate it when the run
    # discards per-round diagnostics (cfg.lean_metrics) -- bit-parity when on
    with obs_trace.stage("engine.delta_norm"):
        delta_norm = jnp.zeros(()) if cfg.lean_metrics else \
            flat.tree_norm(spec, participation.aggregate(part, deltas))
    # the telemetry bus (repro.obs): pure reductions over buffers this tail
    # already holds; None when disabled -- an empty subtree, no new leaves
    telemetry = None
    if cfg.obs.enabled:
        with obs_trace.stage("round.telemetry"):
            telemetry = obs_bus.round_telemetry(
                cfg, deltas, e_up, x_new, wf, w_new_f, g_hat, sigma,
                uplink, downlink, slot_stats)
    with obs_trace.stage("engine.bookkeeping"):
        metrics = RoundMetrics(
            f=f_part, g_hat=g_hat, g_full=g_full, sigma=sigma,
            feasible=(g_hat <= cfg.switch.eps).astype(jnp.float32),
            delta_norm=delta_norm,
            up_bytes=jnp.asarray(float(uplink.wire_bytes()), jnp.float32),
            down_bytes=jnp.asarray(float(downlink.wire_bytes()),
                                   jnp.float32),
            f_full=f_full, telemetry=telemetry)

        new_state = FedState(
            w=w_new, x=x_keep, e_up=e_up,
            wbar_sum=wbar_sum, wbar_weight=state.wbar_weight + alpha,
            t=state.t + 1, key=key, sampler=samp_state)
    return new_state, metrics


def round_step(state: FedState,
               batches,
               loss_pair: Callable,   # (params, batch) -> (f_j, g_j) scalars
               cfg: FedConfig) -> tuple[FedState, RoundMetrics]:
    """One engine round.  ``batches`` has leading axis [n_clients], or is a
    :class:`repro.fleet.Fleet` -- then this round's per-client minibatches
    are provisioned in-jit from the fleet's shards (fleet.provision).

    The round is a composition of the stage helpers above
    (:func:`sample_round` / :func:`compute_round` / :func:`finish_round`),
    shared with the asynchronous round in engine.async_rounds -- only the
    wire path between the stages differs there (split encode/reduce with
    the staleness-buffer merge).  Between sampling and the next FedState the
    model lives as ONE contiguous [d] buffer (comm.flat): local steps, EF
    residual arithmetic, aggregation and the server/downlink updates are
    single fused operations over it."""
    strat = strategies.get_strategy(cfg.strategy)
    strat.validate(cfg)
    key, k_part, k_up, k_down = jax.random.split(state.key, 4)

    with obs_trace.stage("round.sample_round"):
        part, samp_state, fleet = sample_round(state, batches, k_part, cfg)
    spec = flat.spec_of(state.w)
    wf = flat.flatten(spec, state.w)
    (batches, pre_gathered, f_part, g_hat, g_full, f_full, sigma,
     deltas) = compute_round(state, wf, spec, batches, fleet, part, strat,
                             loss_pair, cfg)

    # -- the wire path: exactly one uplink and one downlink call site -------
    # All compressor / backend / wire-format dispatch lives inside the
    # transport layer (repro.comm / comm.flat); participation-mode dispatch
    # lives in engine.participation.
    uplink, downlink = flat_transports_for(cfg, spec)
    with obs_trace.stage("round.encode_reduce"):
        v_bar, e_up, slot_stats = participation.transmit(
            uplink, state.e_up, deltas, part, like=wf, key=k_up, t=state.t)

    return finish_round(state, strat, cfg, spec, wf, part, deltas, v_bar,
                        e_up, uplink, downlink, samp_state, key, k_down,
                        f_part, g_hat, g_full, f_full, sigma,
                        slot_stats=slot_stats)


# ---------------------------------------------------------------------------
# Drivers
# ---------------------------------------------------------------------------

def drive(state: FedState, batches, loss_pair: Callable, cfg: FedConfig,
          T: int, *, per_round: bool = False, block: int = 0,
          progress: Optional[Callable] = None,
          donate: Optional[bool] = None,
          on_chunk: Optional[Callable] = None):
    """Fully-jitted multi-round driver: lax.scan over rounds with donated
    state buffers, metric offload per ``block`` rounds, and an optional
    host-callback progress hook.

    * ``batches``: fixed per-client data ([n, ...]), or a
      :class:`repro.fleet.Fleet` -- each scanned round then provisions
      fresh per-client minibatches in-jit (no per-round host transfers;
      set ``cfg.fleet.redraw`` for per-round re-draws); with
      ``per_round=True`` a stacked [T, n, ...] pytree scanned one slice
      per round (array batches only).
    * ``block``: rounds per scan segment.  Metrics transfer to the host once
      per segment (device metric memory is O(block), and the per-round
      dispatch stall of the old host loop is amortized away).  0 => one
      segment of T rounds.
    * ``progress``: ``progress(t, f, g_hat, sigma)`` called from the device
      via ``jax.debug.callback`` every round (``ordered=True``: lines
      cannot reorder across rounds or scan segments).
    * ``donate``: donate the state buffers to each scan segment (defaults to
      on for non-CPU backends; CPU ignores donation and would warn).  The
      caller's state is copied once up front so donation never invalidates
      caller-held arrays (FedState.w aliases the params it was built from).
    * ``on_chunk``: host callback receiving each offloaded metric segment
      (numpy, [<=block] leading axis) as it lands -- the metrics-sink hook
      (repro.obs.sinks), so live sinks see telemetry at ``block``
      granularity instead of end-of-run.

    Returns ``(final_state, metrics)`` with metrics stacked on the host
    ([T] leading axis, numpy).
    """
    step = lambda c, b: round_step(c, b, loss_pair, cfg)  # noqa: E731
    carry = state
    progress_of = lambda c, mets: (c.t, mets.f, mets.g_hat,  # noqa: E731
                                   mets.sigma)
    if cfg.obs.enabled:
        # the trailing switching-fraction ring rides the loop carry (the
        # FedState itself is untouched -- state parity is unconditional)
        step = obs_bus.window_wrap(
            step, cfg, sigma_of=lambda m: m.sigma,
            tel_get=lambda m: m.telemetry,
            tel_set=lambda m, tel: m._replace(telemetry=tel))
        carry = (state, obs_bus.ring_init(cfg))
        progress_of = lambda c, mets: (c[0].t, mets.f,  # noqa: E731
                                       mets.g_hat, mets.sigma)
    carry, mets = _drive_loop(
        step, carry, batches, T, per_round=per_round, block=block,
        progress=progress, progress_of=progress_of, donate=donate,
        on_chunk=on_chunk)
    return (carry[0] if cfg.obs.enabled else carry), mets


def _drive_loop(step: Callable, carry, batches, T: int, *,
                per_round: bool = False, block: int = 0,
                progress: Optional[Callable] = None,
                progress_of: Optional[Callable] = None,
                donate: Optional[bool] = None,
                on_chunk: Optional[Callable] = None):
    """The shared scan machinery behind :func:`drive` and
    ``async_rounds.async_drive``: lax.scan segments over ``step(carry, b)
    -> (carry, mets)`` with donated carry buffers, per-``block`` metric
    offload (each host segment also fed to ``on_chunk`` -- the sink hook),
    and the ``jax.debug.callback`` progress hook
    (``progress(*progress_of(carry, mets))`` per round, ``ordered=True``
    so lines cannot reorder within or across scan segments)."""
    if donate is None:
        donate = jax.default_backend() != "cpu"
    if donate:
        carry = tree_map(jnp.copy, carry)
    block = int(block) if block else T
    block = max(1, min(block, T))

    def segment(length: int):
        def run(c, xs):
            def body(carry, x):
                b = x if per_round else batches
                carry, mets = step(carry, b)
                if progress is not None:
                    jax.debug.callback(progress, *progress_of(carry, mets),
                                       ordered=True)
                return carry, mets
            return jax.lax.scan(body, c, xs,
                                length=None if per_round else length)
        kw = {"donate_argnums": (0,)} if donate else {}
        return jax.jit(run, **kw)

    runners: dict = {}
    chunks = []
    t = 0
    while t < T:
        L = min(block, T - t)
        if L not in runners:
            runners[L] = segment(L)
        xs = None
        if per_round:
            xs = tree_map(lambda x: x[t:t + L], batches)
        carry, mets = runners[L](carry, xs)
        host = jax.device_get(mets)             # offload one segment
        chunks.append(host)
        if on_chunk is not None:
            on_chunk(host)
        t += L
    stacked = tree_map(lambda *xs: np.concatenate(xs, axis=0), *chunks)
    return carry, stacked


def round_program(loss_pair: Callable, cfg: FedConfig):
    """The jitted one-round program :func:`run_rounds` drives.  On every
    backend but the CPU (which ignores donation) it takes the state's
    buffers, so a full-width state is never held twice; the caller's state
    is consumed."""
    donate = (0,) if jax.default_backend() != "cpu" else ()
    return jax.jit(lambda s, b: round_step(s, b, loss_pair, cfg),
                   donate_argnums=donate)


def run_rounds(state: FedState, batch_fn: Callable, loss_pair: Callable,
               cfg: FedConfig, T: int, jit: bool = True,
               step: Optional[Callable] = None):
    """Drive T rounds; ``batch_fn(t, key) -> batches`` supplies per-round
    data (host-side loop so batch_fn may be arbitrary python; the round
    itself is jitted -- ``step`` passes a :func:`round_program` built by the
    caller).

    Compatibility shim over the engine round.  Metrics accumulate on device
    and transfer to the host once at the end -- the seed's per-round
    ``jax.device_get`` stalled dispatch between rounds.
    """
    if step is None:
        step = round_program(loss_pair, cfg) if jit else \
            (lambda s, b: round_step(s, b, loss_pair, cfg))
    history = []
    key = jax.random.PRNGKey(cfg.seed + 1)
    for t in range(T):
        key, sub = jax.random.split(key)
        batches = batch_fn(t, sub)
        state, metrics = step(state, batches)
        history.append(metrics)                 # stays on device
    stacked = tree_map(lambda *xs: jnp.stack(xs), *history)
    return state, jax.device_get(stacked)


def run_rounds_scan(state: FedState, batches, loss_pair: Callable,
                    cfg: FedConfig, T: int):
    """Fully-jitted T rounds with fixed per-client data -- compatibility
    shim over :func:`drive` (the fast path for the paper's full-batch NP
    experiments)."""
    return drive(state, batches, loss_pair, cfg, T)


def round_bytes(params, cfg: FedConfig) -> dict:
    """Wire-bytes accounting for one round (per participating client).

    ``uplink``/``downlink`` are analytic estimates (message_bytes);
    ``measured_up``/``measured_down`` come from the engine's actual wire
    representation (the flat payloads of comm.flat: bit-packed uint32
    quantizer words, uint16 block offsets) for this config's backend."""
    spec = flat.spec_of(params)
    uplink, downlink = flat_transports_for(cfg, spec)
    up = message_bytes(params, cfg.uplink)
    down = message_bytes(params, cfg.downlink)
    dense = message_bytes(params, type(cfg.uplink)(kind="none"))
    return {"uplink": up, "downlink": down, "dense": dense,
            "measured_up": uplink.wire_bytes(),
            "measured_down": downlink.wire_bytes(),
            "savings_up": 1.0 - up / dense, "savings_down": 1.0 - down / dense}
