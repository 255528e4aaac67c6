"""Train / serve step builders — where the paper's technique meets the model.

Distribution model (see DESIGN.md §5):

* Within a pod: GSPMD — params 2-D sharded (FSDP over ``data``, TP/EP over
  ``model``); XLA inserts exact reduce-scatters for the intra-pod gradient
  reduction (fast ICI — compression not worth it there; App. I trade-off).
* Across pods: params are replicated, the gradient reduction crosses the
  slow inter-pod links — this is where Algorithm 1's quantized exchange is
  applied, via ``shard_map`` over the ``pod`` axis with automatic GSPMD for
  the inner axes.  ``axis_name="data"`` gives the paper's original
  DDP-over-Ethernet setting (params replicated over data; used by the CPU
  examples with 8 host devices).

The exchange is configured through the unified Exchange API
(:mod:`repro.core.exchange`): ``make_train_step(..., exchange=ExchangeConfig(...))``
returns a step with the uniform signature

    step(params, opt_state, ex_state, batch, key)
        -> (params, opt_state, ex_state, metrics)

threading the explicit :class:`ExchangeState` pytree (level tables + QAda
sufficient statistics) through every call — which is what makes adaptive
level schedules available in model-scale training.  ``metrics`` carries
``wire_bytes``: the analytic collective-operand bytes this device moved
this step (asserted equal to the trace-time wire recorder in tests).

Optimizers: the ExtraAdam family (the paper's experimental instantiation)
and ``qgenx`` — the paper's OWN adaptive-step-size extragradient
(:mod:`repro.optim.qgenx`, Theorems 3/4) running on real models.  The
``qgenx`` oracle schedule is a method-engine choice
(:mod:`repro.core.methods`, ``--method`` on the train CLI): ``de``
(Example 3.2) compresses BOTH broadcast rounds of the extra-gradient step
(2 oracle calls/step), ``optda`` (Example 3.3) reuses the previous
half-step feedback carried in ``QGenXOptState.prev_half`` and pays ONE
oracle call and one broadcast round per step.

Every tree exchange this step performs — the gradient ``pmean_tree``
calls of all optimizer branches AND the ``recenter_every`` parameter
re-centering — routes through the compressor's static ExchangePlan
(:mod:`repro.core.exchange_plan`, ``ExchangeConfig.use_plan``): the
gradient pytree is packed ONCE into a tile-aligned flat buffer whose
layout XLA sees unchanged every step (with the train CLI donating
params/opt_state/ex_state, buffers are reused across steps rather than
reallocated), bit-exact with the per-call concatenate+pad path it
replaces.  ``--no-exchange-plan`` is the escape hatch.

Local-update regime (``ExchangeConfig.sync_every = K``): workers take K
local (extra)gradient steps between compressed exchanges.  The exchanges
are gated behind ``lax.cond`` on the optimizer step counter, so collective
traffic (and the ``wire_bytes`` metric) drops to ~1/K; on sync steps a
small f32 probe of the params is pmean'd (recorded as wire traffic) to
emit ``metrics["param_drift"]`` — the RMS per-coordinate deviation of the
drifted local params from their cross-worker mean.  ``sync_every=1`` is
byte-identical to the ungated path (no cond in the jaxpr).

Error feedback (``--compressor ef21-topk`` / ``ef-randk``): the
contractive compressors carry per-worker memory in ``ExchangeState.error``
(sized by ``Exchange.init_state(template=params, num_workers=axis_size)``
— the train CLI does this).  Its semantics fall out of the existing state
threading: non-sync local steps carry ``ex_state`` through ``lax.cond``
untouched (memory only advances on real exchanges), and a guard-rejected
step restores the PRE-exchange state, so rejected steps never advance
error memory.  ``recenter_every`` and partial-participation masks are
rejected loudly at build/trace time for these compressors, and the qgenx
gamma statistic switches to the compensated (exchanged) estimates — the
raw local gradients are not a proxy for what the EF recursion applies.
"""

from __future__ import annotations

from typing import Optional, Union

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import PartitionSpec as P

from repro.core import faults as faults_mod
from repro.core.exchange import (
    Exchange,
    ExchangeConfig,
    make_exchange,
    record_wire,
)
from repro.core.extragradient import adaptive_gamma
from repro.core.methods import commit_params, get_method
from repro.core.quantization import QuantConfig
from repro.models.model import Model
from repro.optim import optimizers as opt
from repro.optim import qgenx as qgenx_opt

Array = jax.Array


def cross_entropy_loss(logits: Array, labels: Array, aux: Array) -> Array:
    ll = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    # the label's log-probability as a one-hot contraction (exact: one
    # term, the rest zeros), not a gather: XLA's SPMD partitioner aborts
    # on batched gathers inside a partially-manual shard_map (the
    # multi-pod exchange)
    pick = jax.nn.one_hot(labels, ll.shape[-1], dtype=ll.dtype)
    nll = -jnp.sum(ll * pick, axis=-1)
    return jnp.mean(nll) + 0.01 * aux


def make_loss_fn(model: Model):
    def loss_fn(params, batch):
        logits, aux = model.forward(params, batch)
        return cross_entropy_loss(logits, batch["labels"], aux)

    return loss_fn


def _legacy_exchange_config(
    quant: Optional[QuantConfig],
    compress_axis: Optional[str],
    compress_mode: str,
) -> Optional[ExchangeConfig]:
    """Map the pre-Exchange keyword bundle onto an ExchangeConfig.

    ``quant=None`` with an axis still routes through shard_map (the exact
    FP32 control arm the dryrun's qgenx mode uses).
    """
    if compress_axis is None:
        return None
    return ExchangeConfig(
        compressor="qgenx" if quant is not None else "none",
        quant=quant,
        mode=compress_mode,
        axis_name=compress_axis,
    )


def make_train_step(
    model: Model,
    opt_cfg: opt.OptimizerConfig,
    *,
    exchange: Union[ExchangeConfig, Exchange, None] = None,
    quant: Optional[QuantConfig] = None,  # deprecated: use exchange=
    compress_axis: Optional[str] = None,  # deprecated: use exchange=
    compress_mode: str = "two_phase",  # deprecated: use exchange=
    mesh=None,
    guard: bool = False,
    fault_spec: Optional[faults_mod.FaultSpec] = None,
):
    """Returns step(params, opt_state, ex_state, batch, key)
    -> (params, opt_state, ex_state, metrics).

    With an ``exchange`` configured, the returned function must be jitted
    under ``mesh`` and wraps a shard_map over ``exchange.axis_name``
    (params replicated across it, batch sharded, all other mesh axes left
    to GSPMD via ``auto``).  ``ex_state`` is the ExchangeState from
    ``make_exchange(cfg).init_state()`` (or ``null_exchange_state()`` when
    no exchange is configured — the signature is uniform either way).

    ``guard=True`` arms the NON-FINITE STEP GUARD: the candidate update is
    computed as usual, an all-float-leaves finiteness flag over
    (loss, new params, new optimizer state, new exchange state) is psum'd
    across the exchange axis, and a ``lax.cond`` carries
    params/opt_state/ex_state through UNCHANGED when any alive worker saw
    a non-finite value — including the exchange-call counter, so a
    rejected step does not advance ``sync_every`` gating, the QAda
    histogram/refresh cadence, the re-centering cadence, or (qgenx
    ``optda``) the carried ``prev_half`` half-step feedback.  Metrics gain
    ``rejected`` (1.0 = this step was rejected), ``nonfinite`` (1.0 = ANY
    worker, alive or dropped, produced a non-finite candidate) and
    ``alive`` (workers contributing to the aggregate).  The guard prices
    one ``isfinite`` pass over the carried state per step; ``guard=False``
    (default) keeps the exact unguarded jaxpr.

    ``fault_spec`` (a :class:`repro.core.faults.FaultSpec`) compiles a
    deterministic fault schedule into the step: NaN-poisoned local
    gradients, dropped workers (threaded into the exchange as a liveness
    mask — the aggregate renormalizes over the alive set), and corrupted
    wire buffers.  When the spec carries device events the returned step
    takes ONE extra trailing argument ``fault_step`` (traced int32: the
    train-loop step the schedule is keyed on)::

        step(params, opt_state, ex_state, batch, key, fault_step)
    """
    if exchange is None:
        exchange = _legacy_exchange_config(quant, compress_axis, compress_mode)
    ex = make_exchange(exchange) if isinstance(exchange, ExchangeConfig) else exchange
    needs_fault_step = fault_spec is not None and fault_spec.has_device_events

    if opt_cfg.name == "qgenx" and get_method(opt_cfg.method).name not in (
        "de", "optda",
    ):
        raise ValueError(
            f"make_train_step supports qgenx methods 'de'/'optda', got "
            f"{opt_cfg.method!r} (the 'da' schedule has no model-scale step)"
        )

    loss_fn = make_loss_fn(model)
    if ex is not None and ex.cfg.overlap != "off":
        # Bucketed overlapped exchange: stage the backward explicitly
        # through jax.vjp (numerically identical to value_and_grad — the
        # same cotangent pullback seeded with 1.0) under the ``forward``
        # and ``backward`` scopes, so traces show the overlap.  The
        # overlap itself is a DATA-FLOW property, not a Python-order one:
        # each bucket's quantize+collective chain (issued inside
        # ex.pmean_tree, highest-leaf buckets first — the cotangents
        # backprop produces first) depends only on its own gradient
        # leaves, so XLA's latency-hiding scheduler is free to run bucket
        # k's collective while the remaining cotangent compute of
        # earlier layers is still in flight, instead of serializing one
        # monolithic gather behind the full gradient.
        def grad_fn(p, b):
            with jax.named_scope("forward"):
                loss, pullback = jax.vjp(lambda q: loss_fn(q, b), p)
            with jax.named_scope("backward"):
                (g,) = pullback(jnp.ones_like(loss))
            return loss, g
    else:
        # unstaged: the round's scope holds both passes, and jax names
        # the backward's ops ``transpose(jvp(...))`` inside it
        grad_fn = jax.value_and_grad(loss_fn)
    axis_name = ex.cfg.axis_name if ex is not None else None
    sync_every = ex.cfg.sync_every if ex is not None else 1
    recenter_every = ex.cfg.recenter_every if ex is not None else 0

    def _probe(params):
        """First ``drift_probe`` parameter coordinates as one f32 vector."""
        chunks, have = [], 0
        for l in jax.tree_util.tree_leaves(params):
            if have >= ex.cfg.drift_probe:
                break
            take = min(l.size, ex.cfg.drift_probe - have)
            chunks.append(l.reshape(-1)[:take].astype(jnp.float32))
            have += take
        return jnp.concatenate(chunks)

    def _param_drift(params):
        """RMS per-coordinate deviation of local params from the mean.

        The probe pmean is real collective traffic on sync steps — it is
        recorded at trace time and counted in the wire_bytes metric.
        """
        probe = _probe(params)
        record_wire("drift_probe", probe)
        mean = jax.lax.pmean(probe, axis_name)
        msd = jax.lax.pmean(jnp.mean((probe - mean) ** 2), axis_name)
        return jnp.sqrt(msd)

    def core_step(params, opt_state, ex_state, batch, key, axis_ix=None,
                  fault_step=None):
        k1, k2 = jax.random.split(key)
        st_in = ex_state
        # device position along the exchange axis: a [1] slice of a
        # sharded arange when the caller threads it (partially-manual
        # meshes cannot lower lax.axis_index — see exchange._axis_key);
        # the exchange falls back to lax.axis_index when None
        ix = axis_ix[0] if axis_ix is not None else None
        # fault schedule (when armed): traced predicates of the train-loop
        # step + this worker's position.  mask is None when the spec has
        # no drop events — the exchange keeps its exact unmasked jaxpr.
        mask = None
        if needs_fault_step:
            wix = ix if ix is not None else jnp.int32(0)
            mask = fault_spec.liveness(fault_step, wix)

            def gfn(p, b):
                loss, g = grad_fn(p, b)
                return loss, fault_spec.poison_grads(g, fault_step, wix)
        else:
            gfn = grad_fn
        # local-update gating: exchanges only fire on every sync_every-th
        # optimizer step (the counter rides in every optimizer's state)
        if sync_every > 1:
            is_sync = (opt_state.count % sync_every) == (sync_every - 1)
        else:
            is_sync = None  # statically always-on: ungated PR-2 path

        def exchange_grads(grads, ex_state, key):
            if ex is None:
                return grads, ex_state  # XLA's exact psum handles it

            # pmean_tree routes mode="leafwise" to the sharding-preserving
            # per-leaf path internally (production mesh: inner axes auto)
            def _do(g, st, k):
                m, st = ex.pmean_tree(g, st, k, ix, mask=mask)
                if needs_fault_step:
                    m = fault_spec.corrupt_mean(m, fault_step)
                return m, st

            if is_sync is None:
                return _do(grads, ex_state, key)
            return jax.lax.cond(
                is_sync, _do,
                lambda g, st, k: (g, st),
                grads, ex_state, key,
            )

        n_workers = jax.lax.psum(1, axis_name) if ex is not None else 1
        # Each oracle round runs under its own scope — ``lookahead`` (the
        # first gradient, its exchange, the extrapolation) and ``commit``
        # (the second gradient, its exchange, the update) — with the
        # optimizer's arithmetic under ``update`` inside it; one-call
        # branches have ``commit`` alone.  The device-trace readers of
        # bench/ split the step by these names.
        scope = jax.named_scope
        if opt_cfg.name == "extra_adam":
            with scope("lookahead"):
                loss1, g1 = gfn(params, batch)
                g1, ex_state = exchange_grads(g1, ex_state, k1)
                with scope("update"):
                    params_half = opt.extrapolate(opt_cfg, params, opt_state,
                                                  g1)
            with scope("commit"):
                loss, g2 = gfn(params_half, batch)
                g2, ex_state = exchange_grads(g2, ex_state, k2)
                with scope("update"):
                    new_params, new_state = opt.commit(opt_cfg, params,
                                                       opt_state, g2)
        elif opt_cfg.name == "qgenx" and get_method(opt_cfg.method).uses_prev_half:
            # optda (Example 3.3): the extrapolation feedback is the
            # PREVIOUS half-step exchanged mean carried in the optimizer
            # state — one oracle call and one broadcast round per step
            with scope("commit"):
                ghat1 = opt_state.prev_half
                with scope("update"):
                    params_half = qgenx_opt.extrapolate(
                        opt_cfg, params, opt_state, ghat1, n_workers
                    )
                loss, g2 = gfn(params_half, batch)
                ghat2, ex_state = exchange_grads(g2, ex_state, k2)
                with scope("update"):
                    # sum_k ||Vbar_{t} - g_{k,t+1/2}||^2 — the carried
                    # feedback vs this worker's fresh half-step oracle (at
                    # K=1 uncompressed this is exactly the toy optda
                    # statistic; parity-tested).  Under a CONTRACTIVE
                    # compressor the raw local gradient is not a proxy for
                    # the estimate the recursion applies, so the gamma
                    # statistic uses the compensated (exchanged) estimate
                    # instead — Python-gated to keep the unbiased jaxpr
                    # bit-exact.
                    if ex is not None and ex.compressor.has_error:
                        sq = qgenx_opt.local_sq_diff(ghat1, ghat2)
                    else:
                        sq = qgenx_opt.local_sq_diff(ghat1, g2)
                    if ex is not None:
                        sq = jax.lax.psum(sq, axis_name)
                    new_params, new_state = qgenx_opt.commit(
                        opt_cfg, params, opt_state, ghat2, sq, n_workers,
                        prev_half=ghat2,
                    )
            g2 = ghat2  # for the wire accounting below (same tree shapes)
        elif opt_cfg.name == "qgenx":
            # de (Example 3.2) — the paper's Algorithm 1 on the model:
            # extragradient with the adaptive gamma rule (statistics in
            # the QGenXOptState pytree)
            with scope("lookahead"):
                loss1, g1 = gfn(params, batch)
                ghat1, ex_state = exchange_grads(g1, ex_state, k1)
                with scope("update"):
                    params_half = qgenx_opt.extrapolate(
                        opt_cfg, params, opt_state, ghat1, n_workers
                    )
            with scope("commit"):
                loss, g2 = gfn(params_half, batch)
                ghat2, ex_state = exchange_grads(g2, ex_state, k2)
                with scope("update"):
                    # sum_k ||g_{k,t} - g_{k,t+1/2}||^2 — the gamma-rule
                    # statistic (from the raw local oracles; under a
                    # contractive compressor the COMPENSATED estimates
                    # replace them — the locals are not a proxy for what
                    # the EF recursion actually applies)
                    if ex is not None and ex.compressor.has_error:
                        sq = qgenx_opt.local_sq_diff(ghat1, ghat2)
                    else:
                        sq = qgenx_opt.local_sq_diff(g1, g2)
                    if ex is not None:
                        sq = jax.lax.psum(sq, axis_name)
                    new_params, new_state = qgenx_opt.commit(
                        opt_cfg, params, opt_state, ghat2, sq, n_workers
                    )
            g2 = ghat2  # for the wire accounting below (same tree shapes)
        elif opt_cfg.name == "optimistic_adam":
            with scope("commit"):
                prev = opt_state.prev_half_grad
                with scope("update"):
                    params_half = opt.extrapolate(opt_cfg, params, opt_state,
                                                  prev)
                loss, g2 = gfn(params_half, batch)
                g2, ex_state = exchange_grads(g2, ex_state, k2)
                with scope("update"):
                    new_params, new_state = opt.commit(opt_cfg, params,
                                                       opt_state, g2)
        else:  # adam baseline
            with scope("commit"):
                loss, g2 = gfn(params, batch)
                g2, ex_state = exchange_grads(g2, ex_state, k2)
                with scope("update"):
                    new_params, new_state = opt.adam_step(opt_cfg, params,
                                                          opt_state, g2)

        st_grad = ex_state  # state after the GRADIENT exchanges only —
        # the re-centering exchange below moves a params/Y-shaped tree
        # whose magnitude distribution the gradient pmf does not describe,
        # so the coded-bits metric prices gradient broadcasts alone
        if recenter_every > 0 and ex is not None:
            # compressed parameter re-centering (Beznosikov et al. 2023:
            # compressed iterate sync): every recenter_every-th step the
            # drifted local iterates are exchanged through the SAME
            # compressor registry as the gradients — local-update runs
            # trade drift for wire.  For qgenx the dual accumulator Y is
            # the iterate (X = anchor + gamma Y with anchor/gamma
            # replicated), so re-centering Y re-centers X consistently;
            # the adam family re-centers the params directly.  It ends
            # the commit round: its exchange and arithmetic are the update's.
            with scope("commit"), scope("update"):
                is_rc = (opt_state.count % recenter_every) == (recenter_every - 1)
                k3 = jax.random.fold_in(key, 0x5eed)  # disjoint from split(key)

                if opt_cfg.name == "qgenx":
                    def _recenter(args):
                        p, st, exst = args
                        y_bar, exst = ex.pmean_tree(st.y, exst, k3, ix, mask=mask)
                        gamma = adaptive_gamma(
                            st.sum_sq, n_workers, opt_cfg.gamma_scale
                        )
                        p = commit_params(st.anchor, y_bar, gamma, like=p)
                        return p, st._replace(y=y_bar), exst
                else:
                    def _recenter(args):
                        p, st, exst = args
                        p_bar, exst = ex.pmean_tree(p, exst, k3, ix, mask=mask)
                        return p_bar, st, exst

                new_params, new_state, ex_state = jax.lax.cond(
                    is_rc, _recenter, lambda args: args,
                    (new_params, new_state, ex_state),
                )
        # the step's metrics: loss mean, wire accounting, Theorem-2
        # code length, drift probe
        with scope("step_metrics"):
            drift = jnp.float32(0.0)
            coded = jnp.float32(0.0)
            alive_m = jnp.float32(1.0)
            if ex is not None:
                loss = jax.lax.pmean(loss, axis_name)  # replicated metric
                # analytic per-exchange operand bytes (static shapes) times the
                # number of exchanges this step performed (= step counter delta;
                # 0 on non-sync steps under the local-update regime; the
                # re-centering exchange bumps the counter too, so its bytes
                # are counted by the same formula)
                axis_size = jax.lax.psum(1, axis_name)
                per_call = ex.wire_bytes_tree(g2, axis_size)
                n_calls = (ex_state.step - st_in.step).astype(jnp.float32)
                wire = jnp.float32(per_call) * n_calls
                alive_m = jnp.float32(axis_size)
                if mask is not None:
                    # partial participation: only alive workers transmit — the
                    # fleet's wire bill this step is alive/K of the full one.
                    # (coded_bits_est stays per-worker/unscaled by design: it
                    # estimates what ONE worker's broadcasts would entropy-code
                    # to, not fleet traffic.)
                    alive_m = jax.lax.psum(mask, axis_name)
                    wire = wire * (alive_m / jnp.float32(axis_size))
                # Theorem 2 entropy-coded wire estimate (Section 3.2): what
                # one worker's GRADIENT broadcasts would cost under CODE o Q
                # with an optimal prefix code, alongside the fixed-width
                # wire_bytes actually shipped — per-call x n_grad_calls.
                # g2 is the exchanged mean, the same on every device, so
                # each prices a share of its buckets.  The O(n) pass is
                # gated like the drift probe: under the local-update regime
                # it only runs on sync steps (its result would be
                # multiplied by a traced zero otherwise, which XLA cannot
                # eliminate).
                if ex.cfg.compressor == "qgenx":
                    n_grad_calls = (st_grad.step - st_in.step).astype(jnp.float32)
                    coded_per = ex.coded_bits_tree(
                        g2, st_in, axis_name=axis_name, axis_index=ix,
                        gate=is_sync)
                    coded = coded_per * n_grad_calls
                if is_sync is not None:
                    # drift probe: measured (and paid) only on sync steps —
                    # params provably stay replicated when every step syncs
                    drift = jax.lax.cond(
                        is_sync, _param_drift, lambda p: jnp.float32(0.0), params
                    )
                    n = sum(l.size for l in jax.tree_util.tree_leaves(params))
                    probe_bytes = 4.0 * min(ex.cfg.drift_probe, n)
                    wire = wire + jnp.float32(probe_bytes) * is_sync.astype(jnp.float32)
            else:
                wire = jnp.float32(0.0)
        rejected = jnp.float32(0.0)
        nonfin = jnp.float32(0.0)
        if guard:
            # non-finite step guard: the candidate update is fully
            # computed above; a single all-float-leaves finiteness flag
            # over (loss, params', opt_state', ex_state') is psum'd and
            # the lax.cond below carries the INPUT state through on
            # rejection — including st_in, so a rejected step advances no
            # exchange-call counter (sync_every gating, QAda hist/refresh
            # cadence, recenter cadence) and, for optda, keeps the
            # pre-step prev_half feedback.
            with scope("guard"):
                ok_local = faults_mod.tree_all_finite(
                    loss, new_params, new_state, ex_state
                )
                bad = (~ok_local).astype(jnp.float32)
                if ex is not None:
                    # a dropped worker cannot veto the fleet's step (its local
                    # candidate never entered the aggregate), but it still
                    # shows up in the nonfinite diagnostic
                    bad_alive = bad * mask if mask is not None else bad
                    nonfin_any = jax.lax.psum(bad, axis_name)
                    ok = jax.lax.psum(bad_alive, axis_name) == 0
                else:
                    nonfin_any = bad
                    ok = bad == 0
                nonfin = (nonfin_any > 0).astype(jnp.float32)
                new_params, new_state, ex_state = jax.lax.cond(
                    ok,
                    lambda t: (t[0], t[1], t[2]),
                    lambda t: (t[3], t[4], t[5]),
                    (new_params, new_state, ex_state, params, opt_state, st_in),
                )
                rejected = jnp.float32(1.0) - ok.astype(jnp.float32)
                # a rejected candidate's entropy estimate is an estimate of
                # garbage (NaN pmf): keep the metric stream finite.  wire is
                # NOT zeroed — the candidate's exchange really moved bytes.
                coded = jnp.where(jnp.isfinite(coded), coded, jnp.float32(0.0))
        metrics = {"loss": loss, "wire_bytes": wire, "param_drift": drift,
                   "coded_bits_est": coded, "rejected": rejected,
                   "nonfinite": nonfin, "alive": alive_m}
        return new_params, new_state, ex_state, metrics

    if ex is None:
        if not needs_fault_step:
            return core_step

        def plain_step(params, opt_state, ex_state, batch, key, fault_step):
            return core_step(
                params, opt_state, ex_state, batch, key,
                fault_step=jnp.asarray(fault_step, jnp.int32),
            )

        return plain_step

    assert mesh is not None, "compressed training needs the mesh for shard_map"

    # params/opt_state/ex_state replicated over the compressed axis (pure
    # DP across it); batch sharded on its leading dim; key replicated
    # (folded inside); all OTHER mesh axes stay under automatic (GSPMD)
    # partitioning — shard_map's ``axis_names`` makes only the exchange
    # axis manual.  The sharded arange gives every device its position along
    # the exchange axis WITHOUT lax.axis_index (whose partition-id
    # lowering the SPMD partitioner rejects on partially-manual meshes);
    # the folded value is identical, so so are all downstream bytes.
    metric_specs = {"loss": P(), "wire_bytes": P(), "param_drift": P(),
                    "coded_bits_est": P(), "rejected": P(), "nonfinite": P(),
                    "alive": P()}

    def sharded_step(params, opt_state, ex_state, batch, key, fault_step=None):
        batch_specs = {
            k: P(axis_name, *([None] * (v.ndim - 1))) for k, v in batch.items()
        }
        axis_ix = jnp.arange(mesh.shape[axis_name], dtype=jnp.int32)
        in_specs = [P(), P(), P(), batch_specs, P(), P(axis_name)]
        args = [params, opt_state, ex_state, batch, key, axis_ix]
        if needs_fault_step:
            # the fault schedule's clock: replicated traced int32 — no
            # recompile per step
            in_specs.append(P())
            args.append(jnp.asarray(fault_step, jnp.int32))
        fn = shard_map(
            core_step,
            mesh=mesh,
            in_specs=tuple(in_specs),
            out_specs=(P(), P(), P(), metric_specs),
            check_vma=False,
            axis_names={axis_name},
        )
        return fn(*args)

    return sharded_step


def make_prefill_step(model: Model):
    """Forward-only (inference prefill)."""

    def prefill(params, batch):
        logits, _ = model.forward(params, batch)
        return logits

    return prefill


def make_serve_step(model: Model):
    """One greedy decode step against a KV cache."""

    def serve_step(params, cache, token, pos):
        logits, cache = model.decode_step(params, cache, token, pos)
        next_token = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        return next_token, logits, cache

    return serve_step
