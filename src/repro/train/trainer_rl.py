"""Classic-RL trainer: VACO vs PPO / PPO-KL / SPO / IMPALA (§5.1).

One jit-compiled ``train_phase`` per algorithm, following the paper's
protocol and Table 1 hyper-parameters:

    collect (mixture actors) -> estimate advantages ONCE (algorithm-
    specific) -> num_epochs x num_minibatches SGD -> publish policy.

Algorithm-specific advantage paths:
* ``vaco``    — V-trace realigned to pi_T (Eqs. 14-15), computed once per
                phase; TV-filtered loss (Alg. 1).
* ``ppo``     — GAE on the behavior data + clipped surrogate.
* ``ppo_kl``  — ppo + KL penalty coefficient (the Fig. 3 baselines).
* ``spo``     — GAE + squared-TV penalty, no clip (Xie et al., 2025).
* ``impala``  — V-trace RE-ESTIMATED against the current policy at every
                minibatch update (the costly path of Fig. 2 bottom).
"""
from __future__ import annotations

import dataclasses
from dataclasses import dataclass
from typing import Any, Callable, Dict, NamedTuple, Tuple

import jax
import jax.numpy as jnp

from repro.core.gae import gae, normalize_advantages
from repro.core.losses import (
    IMPALAConfig,
    PPOConfig,
    SPOConfig,
    VACOConfig,
    impala_total_loss,
    ppo_total_loss,
    spo_total_loss,
    vaco_total_loss,
)
from repro.core.vtrace import vtrace, vtrace_impala_pg_advantage
from repro.kernels import ops as kops
from repro.models.mlp_policy import policy_dist, value_fn
from repro.optim import (
    AdamWConfig,
    AdamWState,
    adamw_init,
    adamw_update,
    clip_by_global_norm,
    linear_anneal,
)
from repro.rollout.env_rollout import RolloutBatch


@dataclass(frozen=True)
class RLHyperparams:
    """Table 1 defaults (CleanRL), scaled for CPU via the runner."""

    algorithm: str = "vaco"
    gamma: float = 0.99
    gae_lambda: float = 0.95
    vtrace_lambda: float = 1.0
    rho_bar: float = 1.0
    c_bar: float = 1.0
    delta: float = 0.2           # clip ratio / TV threshold
    kl_coef: float = 0.0         # ppo_kl
    spo_coef: float = 20.0
    entropy_coef: float = 0.0
    value_coef: float = 0.5
    lr: float = 3e-4
    max_grad_norm: float = 0.5
    num_epochs: int = 10
    num_minibatches: int = 32
    total_phases: int = 100      # for LR annealing
    normalize_adv: bool = True   # PPO-family minibatch normalization
    realign: bool = True         # Fig. 12 ablation: False => GAE advantages
                                 # on behavioral data + TV filter only


class RLTrainState(NamedTuple):
    params: Any
    opt_state: AdamWState
    phase: jax.Array   # int32 counter for LR annealing


def init_train_state(params: Any) -> RLTrainState:
    return RLTrainState(
        params=params,
        opt_state=adamw_init(params),
        phase=jnp.zeros((), jnp.int32),
    )


def _log_pi_and_entropy(params, obs, actions):
    dist = policy_dist(params, obs)
    return dist.log_prob(actions), dist.entropy()


def _phase_advantages(hp: RLHyperparams, params, batch: RolloutBatch):
    """Advantage/value-target estimation at phase start (once)."""
    values = value_fn(params, batch.obs)                      # [N, T]
    bootstrap = value_fn(params, batch.final_obs)             # [N]
    discounts = hp.gamma * (1.0 - batch.dones.astype(jnp.float32))

    if hp.algorithm == "vaco" and hp.realign:
        log_pi_T, _ = _log_pi_and_entropy(params, batch.obs, batch.actions)
        log_ratios = log_pi_T - batch.log_beta
        # kernels.ops dispatches the jnp reference (CPU) or the Pallas
        # kernel (TPU) by platform; realignment is once-per-phase
        # and consumed under stop_gradient, so the no-autodiff kernel
        # path is safe here.
        vs, advantages = kops.vtrace(
            jax.lax.stop_gradient(log_ratios), values, bootstrap,
            batch.rewards, discounts, rho_bar=hp.rho_bar, c_bar=hp.c_bar,
            lam=hp.vtrace_lambda,
        )
        return advantages, vs
    # PPO-family: GAE on the behavioral data.
    out = gae(values=values, bootstrap_value=bootstrap,
              rewards=batch.rewards, discounts=discounts,
              lam=hp.gae_lambda)
    return out.advantages, out.returns


def make_train_phase(
    hp: RLHyperparams,
) -> Callable[[RLTrainState, RolloutBatch, jax.Array],
              Tuple[RLTrainState, Dict[str, jax.Array]]]:
    """Build the jitted phase update for `hp.algorithm`."""
    opt_cfg = AdamWConfig(lr=hp.lr, eps=1e-5)
    lr_schedule = linear_anneal(hp.total_phases, floor=0.0)

    vaco_cfg = VACOConfig(delta=hp.delta, entropy_coef=hp.entropy_coef,
                          value_coef=hp.value_coef)
    ppo_cfg = PPOConfig(clip_low=hp.delta, clip_high=hp.delta,
                        kl_coef=hp.kl_coef if hp.algorithm == "ppo_kl"
                        else 0.0,
                        entropy_coef=hp.entropy_coef,
                        value_coef=hp.value_coef)
    spo_cfg = SPOConfig(penalty_coef=hp.spo_coef,
                        entropy_coef=hp.entropy_coef,
                        value_coef=hp.value_coef)
    impala_cfg = IMPALAConfig(entropy_coef=hp.entropy_coef,
                              value_coef=hp.value_coef,
                              rho_bar_pg=hp.rho_bar)

    def minibatch_loss(params, mb, full_batch):
        """mb: dict of flat [M, ...] slices."""
        log_pi, entropy = _log_pi_and_entropy(
            params, mb["obs"], mb["actions"])
        values = value_fn(params, mb["obs"])

        if hp.algorithm == "vaco":
            return vaco_total_loss(
                log_pi=log_pi, log_beta=mb["log_beta"],
                advantages=mb["advantages"] * mb["weight"], values=values,
                value_targets=mb["value_targets"], cfg=vaco_cfg,
            )
        if hp.algorithm in ("ppo", "ppo_kl"):
            adv = mb["advantages"]
            if hp.normalize_adv:
                adv = normalize_advantages(adv)
            return ppo_total_loss(
                log_pi=log_pi, log_beta=mb["log_beta"],
                advantages=adv * mb["weight"],
                values=values, value_targets=mb["value_targets"],
                entropy=entropy, cfg=ppo_cfg,
            )
        if hp.algorithm == "spo":
            adv = mb["advantages"]
            if hp.normalize_adv:
                adv = normalize_advantages(adv)
            adv = adv * mb["weight"]
            return spo_total_loss(
                log_pi=log_pi, log_beta=mb["log_beta"], advantages=adv,
                values=values, value_targets=mb["value_targets"],
                entropy=entropy, cfg=spo_cfg,
            )
        if hp.algorithm == "impala":
            # Re-estimate V-trace against the CURRENT policy on the full
            # batch (this is IMPALA's per-update realignment cost).
            full_values = value_fn(params, full_batch.obs)
            full_boot = value_fn(params, full_batch.final_obs)
            discounts = hp.gamma * (
                1.0 - full_batch.dones.astype(jnp.float32))
            full_log_pi, _ = _log_pi_and_entropy(
                params, full_batch.obs, full_batch.actions)
            log_ratios = jax.lax.stop_gradient(full_log_pi) - \
                full_batch.log_beta
            out = vtrace(
                log_ratios=log_ratios, values=full_values,
                bootstrap_value=full_boot, rewards=full_batch.rewards,
                discounts=discounts, rho_bar=hp.rho_bar, c_bar=hp.c_bar,
                lam=hp.vtrace_lambda,
            )
            pg_adv = vtrace_impala_pg_advantage(
                out, rewards=full_batch.rewards, discounts=discounts,
                values=full_values, bootstrap_value=full_boot,
                rho_bar_pg=hp.rho_bar, log_ratios=log_ratios,
            )
            flat = lambda x: x.reshape(-1, *x.shape[2:])
            idx = mb["flat_idx"]
            return impala_total_loss(
                log_pi=log_pi, log_beta=mb["log_beta"],
                pg_advantages=flat(pg_adv)[idx] * mb["weight"],
                values=values,
                value_targets=jax.lax.stop_gradient(flat(out.vs))[idx],
                entropy=entropy, cfg=impala_cfg,
            )
        raise ValueError(hp.algorithm)

    grad_fn = jax.value_and_grad(minibatch_loss, has_aux=True)

    def train_phase(state: RLTrainState, batch: RolloutBatch, key,
                    weight: float = 1.0):
        """One phase update.  `weight` scales the policy-gradient
        advantages — 1.0 normally; <1 when the runtime's admission policy
        downweighted the trajectory item instead of dropping it."""
        advantages, value_targets = _phase_advantages(
            hp, state.params, batch)
        advantages = jax.lax.stop_gradient(advantages)
        value_targets = jax.lax.stop_gradient(value_targets)

        n, t = batch.rewards.shape
        flat = lambda x: x.reshape(n * t, *x.shape[2:])
        data = {
            "obs": flat(batch.obs),
            "actions": flat(batch.actions),
            "log_beta": flat(batch.log_beta),
            "advantages": flat(advantages),
            "value_targets": flat(value_targets),
            "flat_idx": jnp.arange(n * t),
            "weight": jnp.full((n * t,), weight, jnp.float32),
        }
        mb_size = (n * t) // hp.num_minibatches
        lr_scale = lr_schedule(state.phase)

        def epoch_step(carry, key_e):
            params, opt_state = carry
            perm = jax.random.permutation(key_e, n * t)
            perm = perm[: mb_size * hp.num_minibatches].reshape(
                hp.num_minibatches, mb_size)

            def mb_step(carry, idx):
                params, opt_state = carry
                mb = {k: v[idx] for k, v in data.items()}
                (loss, aux), grads = grad_fn(params, mb, batch)
                grads, gnorm = clip_by_global_norm(
                    grads, hp.max_grad_norm)
                params, opt_state = adamw_update(
                    grads, opt_state, params, opt_cfg, lr_scale)
                aux = dict(aux, grad_norm=gnorm)
                return (params, opt_state), aux

            (params, opt_state), auxs = jax.lax.scan(
                mb_step, (params, opt_state), perm)
            return (params, opt_state), auxs

        keys = jax.random.split(key, hp.num_epochs)
        (params, opt_state), auxs = jax.lax.scan(
            epoch_step, (state.params, state.opt_state), keys)

        metrics = {k: jnp.mean(v) for k, v in auxs.items()}
        metrics["mean_reward"] = jnp.mean(batch.rewards)
        # Final-policy TV vs the behavior data (Fig. 11 diagnostic).
        log_pi, _ = _log_pi_and_entropy(params, batch.obs, batch.actions)
        metrics["final_tv"] = 0.5 * jnp.mean(
            jnp.abs(jnp.exp(log_pi - batch.log_beta) - 1.0))
        new_state = RLTrainState(
            params=params, opt_state=opt_state, phase=state.phase + 1)
        return new_state, metrics

    return jax.jit(train_phase)
