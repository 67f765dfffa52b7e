"""RLVR trainer (§5.2): GRPO with PPO-clip vs GRPO with VACO filtering.

Protocol (Noukhovitch et al., 2025 / paper App. C.2): each phase freezes
the policy as β, generates N minibatches of grouped completions, labels
them with the binary verifier, then takes N updates — minibatch k is
consumed with forward lag k.  Table 2 hyper-parameters are defaults
(clip 0.2/0.272 DAPO-style; TV threshold δ=0.05; 1 PPO epoch).

The trainer runs on the unified async runtime: the serve side
(``ForwardLagGenerator.generate_minibatch``) produces into a
staleness-tagged :class:`TrajectoryQueue` under a lag regime —
``forward_n`` reproduces the paper's phase-locked schedule (bit-for-bit
vs. the legacy in-process loop at fixed seed), ``threaded`` runs a real
producer thread against the consuming learner.  Every update publishes a
new version to the :class:`PolicyStore`; item staleness is read off the
queue tags instead of being scripted.

Because no pretrained base model is downloadable offline, the runner
first *creates* a base model with a supervised warm-start on synthetic
chain traces (repro.data.mathgen), then runs RL exactly as the paper does
on Qwen2.5-0.5B-base.  The advantage realignment ratio is 1 (fresh data
each phase — no backward lag), matching App. C.2.
"""
from __future__ import annotations

import time
from dataclasses import dataclass, field
from typing import Any, Dict, List, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.losses import GRPOConfig, group_advantages, grpo_token_loss
from repro.core.tv_filter import tv_estimate
from repro.data.mathgen import MathTaskDataset
from repro.metrics.runtime_metrics import collect_runtime_stats
from repro.models.registry import ModelBundle
from repro.obs.registry import MetricsRegistry
from repro.obs.tracer import NULL_TRACER, Tracer
from repro.checkpoint import (
    latest_checkpoint,
    load_checkpoint,
    save_checkpoint,
)
from repro.optim import (
    AdamWConfig,
    AdamWState,
    adamw_init,
    adamw_update,
    clip_by_global_norm,
)
from repro.resilience import (
    BackoffPolicy,
    FaultInjector,
    NULL_INJECTOR,
    tree_all_finite,
)
from repro.rollout.async_engine import ForwardLagGenerator, RLVRMinibatch
from repro.rollout.sampler import score_tokens
from repro.runtime import (
    PolicyStore,
    TrajectoryQueue,
    make_controller,
    make_regime,
    parse_controller_spec,
    spec_from_legacy,
)
from repro.runtime.serve_producer import ServeRolloutProducer


@dataclass(frozen=True)
class RLVRHyperparams:
    algorithm: str = "grpo"       # grpo (ppo-clip) | grpo_vaco
    clip_low: float = 0.2
    clip_high: float = 0.272      # DAPO clip-higher
    delta: float = 0.05           # VACO TV threshold (Table 2)
    entropy_coef: float = 0.0
    lr: float = 1e-4              # paper: 1e-6 on a 0.5B; scaled for ~1M
    weight_decay: float = 0.0
    max_grad_norm: float = 1.0
    n_minibatches: int = 4        # N — the forward-lag knob
    prompts_per_minibatch: int = 16   # paper: 32
    completions_per_prompt: int = 4   # paper: 8
    max_new_tokens: int = 8
    temperature: float = 1.0
    warmup_steps: int = 300       # supervised base-model creation
    warmup_lr: float = 3e-3
    warmup_batch: int = 64
    eval_prompts: int = 256       # greedy-accuracy eval set size
    # --- runtime ---
    runtime: str = "forward_n"    # forward_n | threaded
    store_capacity: int = 4       # policy snapshot ring size
    queue_maxsize: int = 4        # producer backpressure (threaded)
    # Lag controller: a "name:key=val,..." spec (see
    # runtime.controllers).  None falls back to the legacy admission
    # triple below via the deprecation shim.
    controller: Optional[str] = None
    # --- legacy admission triple (deprecated; use `controller`) ---
    admission: str = "pass_through"  # pass_through|max_lag|tv_gate
    #                                 # |tv_gate_tokenwise
    max_lag: int = 8
    admission_mode: str = "drop"  # tv_gate*: drop|downweight
    get_timeout: float = 300.0    # learner wait per item (threaded)
    max_refills: int = 50         # phase-locked starvation bound
    # --- producer ---
    producer: str = "legacy"      # legacy (ForwardLagGenerator) | serve
    # serve producer: force generation from the learner's k-back
    # snapshot (None = track the freshest swapped-in weights).
    forced_lag: Optional[int] = None
    engine_num_blocks: int = 64   # serve producer: paged-pool size
    engine_block_size: int = 8
    engine_max_batch: int = 8
    engine_swap_interval: int = 1
    engine_prefix_cache: bool = False
    engine_speculate_k: int = 0
    # --- resilience (see repro.resilience) ---
    # Fault plan spec: ";"-joined "kind:key=val,..." chunks, e.g.
    # "producer_crash:at_step=40;nan_publish:at_publish=7".  Empty =
    # no injection (NULL_INJECTOR, zero overhead on every hook).
    fault_plan: str = ""
    fault_seed: int = 0
    # Watchdog: >0 supervises threaded producers with bounded-retry
    # restarts under seeded exponential backoff; 0 = crash-fast (the
    # pre-supervision behavior, and what phase-locked runs use).
    watchdog_restarts: int = 0
    watchdog_backoff_ms: float = 50.0
    # Serve producer: per-request wall-clock budget; timed-out requests
    # retire with finish_reason="timeout" and release their pages.
    request_deadline_s: Optional[float] = None
    # Quarantine non-finite publishes and skip+restore non-finite
    # learner steps (restores the last finite state, or the newest
    # checkpoint under `guard_checkpoint_dir` when set).
    finiteness_guard: bool = True
    guard_checkpoint_dir: Optional[str] = None


class RLVRTrainState(NamedTuple):
    params: Any
    opt_state: AdamWState
    updates: jax.Array


def _make_grad_fn(bundle: ModelBundle, hp: RLVRHyperparams,
                  prompt_len: int):
    """``value_and_grad`` of the GRPO/VACO token loss (with aux)."""
    grpo_cfg = GRPOConfig(
        clip_low=hp.clip_low, clip_high=hp.clip_high,
        use_vaco=(hp.algorithm == "grpo_vaco"), delta=hp.delta,
        entropy_coef=hp.entropy_coef,
    )

    def loss_fn(params, tokens, log_beta, mask, advantages):
        # The fused log-prob kernel is forward-only, so the differentiated
        # loss scores through the jnp path on every platform.
        log_pi, entropy, _ = score_tokens(
            bundle, params, tokens, prompt_len, kernel_mode="reference")
        loss, aux = grpo_token_loss(
            log_pi=log_pi, log_beta=log_beta, advantages=advantages,
            token_mask=mask, cfg=grpo_cfg,
        )
        aux["token_entropy"] = jnp.sum(entropy * mask) / jnp.maximum(
            jnp.sum(mask), 1.0)
        return loss, aux

    return jax.value_and_grad(loss_fn, has_aux=True)


def make_update_step(bundle: ModelBundle, hp: RLVRHyperparams,
                     prompt_len: int):
    opt_cfg = AdamWConfig(lr=hp.lr, weight_decay=hp.weight_decay, eps=1e-8)
    grad_fn = _make_grad_fn(bundle, hp, prompt_len)

    @jax.jit
    def update(state: RLVRTrainState, tokens, log_beta, mask, advantages):
        (loss, aux), grads = grad_fn(
            state.params, tokens, log_beta, mask, advantages)
        grads, gnorm = clip_by_global_norm(grads, hp.max_grad_norm)
        params, opt_state = adamw_update(
            grads, state.opt_state, state.params, opt_cfg)
        aux = dict(aux, loss=loss, grad_norm=gnorm)
        return RLVRTrainState(params, opt_state, state.updates + 1), aux

    return update


def make_split_update_step(bundle: ModelBundle, hp: RLVRHyperparams,
                           prompt_len: int):
    """The fused update split at the gradient boundary, for controllers
    with ``needs_gradients`` (GAC): ``grad_step`` returns the raw
    gradients so the controller can inspect/rescale them on the host,
    ``apply_step`` then clips and applies.  Same math as
    :func:`make_update_step`, two dispatches instead of one."""
    opt_cfg = AdamWConfig(lr=hp.lr, weight_decay=hp.weight_decay, eps=1e-8)
    grad_fn = _make_grad_fn(bundle, hp, prompt_len)

    @jax.jit
    def grad_step(state: RLVRTrainState, tokens, log_beta, mask,
                  advantages):
        (loss, aux), grads = grad_fn(
            state.params, tokens, log_beta, mask, advantages)
        return grads, dict(aux, loss=loss)

    @jax.jit
    def apply_step(state: RLVRTrainState, grads):
        grads, gnorm = clip_by_global_norm(grads, hp.max_grad_norm)
        params, opt_state = adamw_update(
            grads, state.opt_state, state.params, opt_cfg)
        return RLVRTrainState(params, opt_state, state.updates + 1), gnorm

    return grad_step, apply_step


def make_warmup_step(bundle: ModelBundle, hp: RLVRHyperparams):
    """Supervised next-token warm-start (creates the 'base model')."""
    opt_cfg = AdamWConfig(lr=hp.warmup_lr, eps=1e-8)

    def loss_fn(params, tokens, mask):
        out = bundle.forward(params, tokens)
        logits = out.logits[:, :-1]
        targets = tokens[:, 1:]
        mask = mask[:, 1:]
        lp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
        nll = -jnp.take_along_axis(lp, targets[..., None], axis=-1)[..., 0]
        return jnp.sum(nll * mask) / jnp.maximum(jnp.sum(mask), 1.0)

    grad_fn = jax.value_and_grad(loss_fn)

    @jax.jit
    def step(state: RLVRTrainState, tokens, mask):
        loss, grads = grad_fn(state.params, tokens, mask)
        grads, _ = clip_by_global_norm(grads, hp.max_grad_norm)
        params, opt_state = adamw_update(
            grads, state.opt_state, state.params, opt_cfg)
        return RLVRTrainState(params, opt_state, state.updates), loss

    return step


@dataclass
class RLVRPhaseLog:
    mean_reward: float
    tv: float
    frac_filtered: float      # VACO filter rate / PPO clip rate
    filter_active: float
    staleness: int            # queue-observed lag at consume time
    weight: float = 1.0       # admission downweight (1.0 = full)


@dataclass
class RLVRResult:
    eval_accuracy: List[float]
    phase_logs: List[RLVRPhaseLog]
    runtime_stats: Dict[str, Any] = field(default_factory=dict)


class RLVRTrainer:
    """Drives warmup + the queue-fed forward-lag RL loop."""

    def __init__(
        self,
        bundle: ModelBundle,
        dataset: MathTaskDataset,
        hp: RLVRHyperparams,
        seed: int = 0,
        tracer: Optional[Tracer] = None,
        metrics: Optional[MetricsRegistry] = None,
    ) -> None:
        self.bundle = bundle
        self.dataset = dataset
        self.hp = hp
        self.tracer = tracer if tracer is not None else NULL_TRACER
        self.metrics = metrics if metrics is not None else MetricsRegistry()
        self._h_step = self.metrics.histogram("train_step_s")
        self.metrics.register_producer(
            "train", lambda: collect_runtime_stats(self.store, self.queue))
        key = jax.random.PRNGKey(seed)
        params = bundle.init(key)
        self.state = RLVRTrainState(
            params=params, opt_state=adamw_init(params),
            updates=jnp.zeros((), jnp.int32),
        )
        self.generator = ForwardLagGenerator(
            bundle, dataset,
            n_minibatches=hp.n_minibatches,
            prompts_per_minibatch=hp.prompts_per_minibatch,
            completions_per_prompt=hp.completions_per_prompt,
            max_new_tokens=hp.max_new_tokens,
            temperature=hp.temperature,
            seed=seed + 1,
            version_fn=lambda: self.store.version,
        )
        self._update = make_update_step(bundle, hp, dataset.prompt_len)
        self._warmup = make_warmup_step(bundle, hp)

        # --- runtime assembly ------------------------------------------------
        # Resilience: one shared injector threads through every fault
        # site (producer, publish, queue, engine, learner); the
        # supervisor policy restarts crashed producer threads with
        # seeded backoff.  Both are inert by default.
        self.injector = (
            FaultInjector(hp.fault_plan, seed=hp.fault_seed,
                          registry=self.metrics, tracer=self.tracer)
            if hp.fault_plan else NULL_INJECTOR)
        self.supervisor = (
            BackoffPolicy(base_ms=hp.watchdog_backoff_ms,
                          max_restarts=hp.watchdog_restarts, seed=seed)
            if hp.watchdog_restarts > 0 else None)
        self._last_good: Optional[RLVRTrainState] = None
        self._learner_steps = 0
        self.nonfinite_skipped = 0
        self.store = PolicyStore(params, capacity=hp.store_capacity,
                                 tracer=self.tracer,
                                 injector=self.injector,
                                 guard_finite=hp.finiteness_guard,
                                 registry=self.metrics)
        # Controller: a spec string wins; the legacy admission triple is
        # mapped through the deprecation shim (no warning here — the
        # launcher warns on actual legacy *flag* use).
        spec = (parse_controller_spec(hp.controller) if hp.controller
                else spec_from_legacy(
                    hp.admission, max_lag=hp.max_lag, delta=hp.delta,
                    mode=hp.admission_mode))
        tv_fn = token_tv_fn = None
        if spec.name == "tv_gate":
            tv_fn = self._make_tv_fn()
        elif spec.name == "tv_gate_tokenwise":
            token_tv_fn = self._make_token_tv_fn()
        self.controller = make_controller(
            spec, tv_fn=tv_fn, token_tv_fn=token_tv_fn)
        self.controller_spec = spec
        self.queue = TrajectoryQueue(
            maxsize=hp.queue_maxsize if hp.runtime == "threaded" else 0,
            admission=self.controller,
            tracer=self.tracer,
            registry=self.metrics,
            injector=self.injector,
            fallback_max_lag=hp.max_lag,
        )
        if self.controller.needs_log_pi:
            prompt_len = dataset.prompt_len

            @jax.jit
            def _score(params, tokens):
                log_pi, _, _ = score_tokens(
                    bundle, params, tokens, prompt_len)
                return log_pi

            self._score_log_pi = _score
        if self.controller.needs_gradients:
            self._grad_step, self._apply_step = make_split_update_step(
                bundle, hp, dataset.prompt_len)
        self.engine = None
        if hp.producer == "serve":
            from repro.serve.engine import ServeEngine

            self.engine = ServeEngine(
                bundle,
                store=self.store,
                num_blocks=hp.engine_num_blocks,
                block_size=hp.engine_block_size,
                max_batch=hp.engine_max_batch,
                max_seq_len=dataset.prompt_len + hp.max_new_tokens,
                swap_interval=hp.engine_swap_interval,
                temperature=hp.temperature,
                seed=seed + 2,
                prefix_cache=hp.engine_prefix_cache,
                speculate_k=hp.engine_speculate_k,
                tracer=self.tracer,
                metrics=self.metrics,
                injector=self.injector,
                request_deadline_s=hp.request_deadline_s,
            )
            self.regime = ServeRolloutProducer(
                self.store, self.queue, self.engine, dataset,
                prompts_per_minibatch=hp.prompts_per_minibatch,
                completions_per_prompt=hp.completions_per_prompt,
                max_new_tokens=hp.max_new_tokens,
                version_offset=hp.forced_lag,
                threaded=(hp.runtime == "threaded"),
                injector=self.injector,
                supervisor=self.supervisor,
            )
        elif hp.producer == "legacy":
            self.regime = make_regime(
                hp.runtime, self.store, self.queue,
                self.generator.generate_minibatch,
                forward_n=hp.n_minibatches,
                max_items=None,
                injector=self.injector,
                supervisor=self.supervisor,
            )
        else:
            raise ValueError(
                f"unknown producer {hp.producer!r} (legacy|serve)")
        self._regime_started = False

    def _make_tv_fn(self):
        """Sequence-level TV of a generated minibatch vs the current policy."""
        bundle, prompt_len = self.bundle, self.dataset.prompt_len

        @jax.jit
        def _tv(params, tokens, log_beta, mask):
            log_pi, _, _ = score_tokens(bundle, params, tokens, prompt_len)
            return tv_estimate(log_pi - log_beta, mask)

        def tv_fn(payload: RLVRMinibatch) -> float:
            params, _ = self.store.latest()
            return float(_tv(params, payload.gen.tokens,
                             payload.gen.log_beta, payload.gen.mask))

        return tv_fn

    def _make_token_tv_fn(self):
        """Per-token TV terms + producing versions for the tokenwise gate.

        Returns ``payload -> (tv_tokens, versions)`` (both flattened over
        the minibatch's mask-valid completion tokens, row-major so a
        row's version run stays contiguous), scoring against the *latest*
        policy in the store — the Eq. 8 estimator the
        ``TokenwiseTVGate`` applies per version segment.
        """
        bundle, prompt_len = self.bundle, self.dataset.prompt_len

        @jax.jit
        def _tv_terms(params, tokens, log_beta):
            log_pi, _, _ = score_tokens(bundle, params, tokens, prompt_len)
            return 0.5 * jnp.abs(jnp.exp(log_pi - log_beta) - 1.0)

        def tv_fn(payload: RLVRMinibatch):
            params, _ = self.store.latest()
            tv = np.asarray(_tv_terms(
                params, payload.gen.tokens, payload.gen.log_beta))
            valid = np.asarray(payload.gen.mask) > 0
            versions = (payload.versions if payload.versions is not None
                        else np.zeros(tv.shape, np.int64))
            return tv[valid], np.asarray(versions)[valid]

        return tv_fn

    def warmup(self, steps: Optional[int] = None) -> float:
        steps = steps if steps is not None else self.hp.warmup_steps
        loss = float("nan")
        for _ in range(steps):
            toks, mask = self.dataset.supervised_batch(
                self.hp.warmup_batch, self.hp.max_new_tokens)
            # reset the optimizer moments only once RL starts.
            self.state, loss = self._warmup(
                self.state, jnp.asarray(toks), jnp.asarray(mask))
        # fresh optimizer state for RL.
        self.state = RLVRTrainState(
            params=self.state.params,
            opt_state=adamw_init(self.state.params),
            updates=jnp.zeros((), jnp.int32),
        )
        # the warm-started model is the RL base policy.
        self.store.publish(self.state.params, event="warmup_done")
        return float(loss)

    def close(self) -> None:
        """Stop the producer (threaded regime) and close the queue."""
        self.regime.stop()

    # -- finiteness guard ----------------------------------------------------

    def _step_finite(self, aux: Dict[str, Any]) -> bool:
        """True when this step's loss and the post-update params are
        all finite (aux values are already on host)."""
        loss = aux.get("loss")
        if loss is not None and not np.all(np.isfinite(np.asarray(loss))):
            return False
        return tree_all_finite(self.state.params)

    def _restore_last_good(self) -> str:
        """Roll the train state back to the newest known-finite point;
        returns where it came from ("checkpoint" | "memory" | "none")."""
        hp = self.hp
        if hp.guard_checkpoint_dir:
            path = latest_checkpoint(hp.guard_checkpoint_dir)
            if path is not None:
                like = {"params": self.state.params,
                        "opt_state": self.state.opt_state}
                tree, _, _ = load_checkpoint(path, like)
                self.state = RLVRTrainState(
                    params=tree["params"],
                    opt_state=tree["opt_state"],
                    updates=self.state.updates)
                return "checkpoint"
        if self._last_good is not None:
            self.state = self._last_good
            return "memory"
        return "none"

    def _skip_nonfinite(self, item: Any) -> None:
        self.nonfinite_skipped += 1
        restored = self._restore_last_good()
        self.metrics.counter(
            "learner_nonfinite_total", restored=restored).inc()
        if self.tracer.enabled:
            self.tracer.instant(
                "learner_nonfinite", pid="train", tid="learner",
                lag=item.lag, restored=restored,
                step=self._learner_steps)

    def train_phase(self) -> List[RLVRPhaseLog]:
        """One train phase: consume N queue items, publish after each.

        Production is lazy: the forward_n regime refills the queue (N
        fresh minibatches from the newest policy) whenever it runs dry,
        which reproduces the legacy generate-N-then-train-N schedule
        exactly when nothing is dropped.
        """
        hp = self.hp
        if not self._regime_started:
            self.regime.start()
            self._regime_started = True
        if hp.finiteness_guard and self._last_good is None:
            self._last_good = self.state
        logs: List[RLVRPhaseLog] = []
        ctrl = self.controller
        self._phase_consumed = 0
        for _ in range(hp.n_minibatches):
            item = self.regime.next_item(
                self.store.version, timeout=hp.get_timeout,
                max_refills=hp.max_refills)
            if item is None:
                break  # producer stopped / everything dropped
            self._phase_consumed += 1
            mb: RLVRMinibatch = item.payload
            adv = group_advantages(
                mb.rewards, hp.completions_per_prompt)
            adv = adv * jnp.float32(item.weight)
            # Controller loss hook: an optional [B, S] per-token
            # multiplier on the advantage.  The default controller
            # returns None, leaving the fused 1-D-advantage update —
            # and its jit cache — byte-identical to the legacy path.
            log_pi = None
            if ctrl.needs_log_pi:
                log_pi = np.asarray(self._score_log_pi(
                    self.state.params, mb.gen.tokens))
            token_w = ctrl.loss_weights(
                item,
                advantages=np.asarray(adv),
                log_beta=np.asarray(mb.gen.log_beta),
                mask=np.asarray(mb.gen.mask),
                log_pi=log_pi,
            )
            adv_in = (adv if token_w is None
                      else adv[:, None] * jnp.asarray(token_w, jnp.float32))
            t0 = time.monotonic()
            with self.tracer.span("learner_step", pid="train", tid="learner",
                                  lag=item.lag, weight=float(item.weight)):
                if ctrl.needs_gradients:
                    grads, aux = self._grad_step(
                        self.state, mb.gen.tokens, mb.gen.log_beta,
                        mb.gen.mask, adv_in)
                    grads, grad_info = ctrl.transform_gradients(item, grads)
                    self.state, gnorm = self._apply_step(self.state, grads)
                    aux = {k: jax.device_get(v) for k, v in aux.items()}
                    aux["grad_norm"] = jax.device_get(gnorm)
                    aux.update(grad_info)
                else:
                    self.state, aux = self._update(
                        self.state, mb.gen.tokens, mb.gen.log_beta,
                        mb.gen.mask, adv_in)
                    aux = {k: jax.device_get(v) for k, v in aux.items()}
            self._h_step.observe(time.monotonic() - t0)
            self._learner_steps += 1
            if self.injector.active:
                poisoned_params, poisoned = self.injector.poison(
                    "learner_step", self.state.params,
                    at_step=self._learner_steps)
                if poisoned:
                    self.state = self.state._replace(
                        params=poisoned_params)
            if hp.finiteness_guard and not self._step_finite(aux):
                # Divergence firewall: drop this update, restore the
                # last finite state, and keep training — the bad step
                # is never published, so generation can't see it.
                self._skip_nonfinite(item)
                continue
            self._last_good = self.state
            if hp.guard_checkpoint_dir:
                save_checkpoint(
                    hp.guard_checkpoint_dir,
                    int(jax.device_get(self.state.updates)),
                    {"params": self.state.params,
                     "opt_state": self.state.opt_state},
                    meta={"source": "finiteness_guard"})
            ctrl.on_learner_step(item, aux)
            self.store.publish(self.state.params)
            frac = aux.get("frac_filtered", aux.get("clip_frac", 0.0))
            logs.append(RLVRPhaseLog(
                mean_reward=float(jnp.mean(mb.rewards)),
                tv=float(aux["tv"]),
                frac_filtered=float(frac),
                filter_active=float(aux.get("filter_active", 1.0)),
                staleness=item.lag,
                weight=float(item.weight),
            ))
        return logs

    def evaluate(self, n: Optional[int] = None) -> float:
        return self.generator.eval_accuracy(
            self.state.params, n if n is not None else self.hp.eval_prompts)

    def train(self, phases: int, eval_every: int = 5) -> RLVRResult:
        accs: List[float] = []
        logs: List[RLVRPhaseLog] = []
        try:
            for i in range(phases):
                phase_logs = self.train_phase()
                logs.extend(phase_logs)
                if not phase_logs:
                    break  # end of stream: no point re-evaluating
                if (i + 1) % eval_every == 0 or i == phases - 1:
                    accs.append(self.evaluate())
                if self._phase_consumed < self.hp.n_minibatches:
                    # Starved mid-phase (producer done / all-drop).
                    # Keyed off items *consumed*, not updates logged: a
                    # finiteness-guard skip shortens the logs but is not
                    # starvation — training must keep going.
                    break
        finally:
            if not self.regime.phase_locked:
                self.close()
        return RLVRResult(
            eval_accuracy=accs, phase_logs=logs,
            runtime_stats=collect_runtime_stats(self.store, self.queue),
        )
