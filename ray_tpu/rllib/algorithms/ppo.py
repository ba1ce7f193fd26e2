"""PPO: clipped-surrogate policy optimization with a jitted JAX learner.

Analog of the reference's rllib/algorithms/ppo (torch loss in
ppo_torch_policy.py): sample via WorkerSet, normalize advantages, run
several epochs of minibatch SGD on the jit-compiled clipped surrogate +
value + entropy loss. On TPU the update jits onto the chip; scaling to a
learner mesh is `pjit` over the batch axis (the reference's multi-GPU
learner thread equivalent, SURVEY.md §2.5).
"""

from __future__ import annotations

from typing import Any, Dict

import numpy as np

from ray_tpu.rllib.algorithms.algorithm import Algorithm
from ray_tpu.rllib.algorithms.algorithm_config import AlgorithmConfig
from ray_tpu.rllib.policy.sample_batch import SampleBatch


class PPOConfig(AlgorithmConfig):
    def __init__(self, algo_class=None):
        super().__init__(algo_class=algo_class or PPO)
        self.clip_param = 0.2
        self.num_sgd_iter = 8
        self.sgd_minibatch_size = 128
        self.vf_loss_coeff = 0.5
        self.entropy_coeff = 0.0
        self.kl_target = 0.02
        self.lambda_ = 0.95
        self.lr = 3e-4
        #: jax backend for the learner's fused SGD program (e.g. "tpu")
        #: while rollouts stay on the process default (cpu) —
        #: the reference's CPU-rollout/GPU-learner split, expressed as
        #: two jax backends in one process. None = process default.
        self.learner_backend = None
        #: >0: a LEARNER GROUP of this many gradient-shard actors
        #: (reference: rl_trainer/trainer_runner.py TrainerRunner +
        #: multi_gpu_learner_thread) — each minibatch splits across
        #: them, gradients average row-weighted, every shard applies
        #: the same averaged update (synchronous DP; optimizer states
        #: stay bit-identical across shards).
        self.num_learners = 0

    def training(self, *, clip_param=None, num_sgd_iter=None,
                 sgd_minibatch_size=None, vf_loss_coeff=None,
                 entropy_coeff=None, learner_backend=None,
                 num_learners=None,
                 **kwargs) -> "PPOConfig":
        super().training(**kwargs)
        if learner_backend is not None:
            self.learner_backend = learner_backend
        if num_learners is not None:
            self.num_learners = num_learners
        if clip_param is not None:
            self.clip_param = clip_param
        if num_sgd_iter is not None:
            self.num_sgd_iter = num_sgd_iter
        if sgd_minibatch_size is not None:
            self.sgd_minibatch_size = sgd_minibatch_size
        if vf_loss_coeff is not None:
            self.vf_loss_coeff = vf_loss_coeff
        if entropy_coeff is not None:
            self.entropy_coeff = entropy_coeff
        return self


def make_ppo_loss(policy, clip: float, vf_coeff: float,
                  ent_coeff: float):
    """The clipped-surrogate PPO loss bound to ``policy`` — shared by
    the central learner here and DDPPO's decentralized worker learners
    (ddppo.py), so the two can never silently diverge. Returns
    ``loss_fn(params, mb) -> (total, metrics)``."""
    import jax
    import jax.numpy as jnp

    def loss_fn(params, mb):
        logp = policy.logp(params, mb["obs"], mb["actions"])
        ratio = jnp.exp(logp - mb["old_logp"])
        adv = mb["advantages"]
        surrogate = jnp.minimum(
            ratio * adv,
            jnp.clip(ratio, 1 - clip, 1 + clip) * adv)
        values = policy._value(params, mb["obs"])
        vf_loss = jnp.mean((values - mb["value_targets"]) ** 2)
        entropy = jnp.mean(policy.entropy(params, mb["obs"]))
        total = (-jnp.mean(surrogate) + vf_coeff * vf_loss
                 - ent_coeff * entropy)
        approx_kl = jnp.mean(mb["old_logp"] - logp)
        return total, {"policy_loss": -jnp.mean(surrogate),
                       "vf_loss": vf_loss, "entropy": entropy,
                       "approx_kl": approx_kl}

    return loss_fn


class _PPOGradShard:
    """One learner-group shard (reference: trainer_runner's RLTrainer
    actor): holds a replica of the policy params + optimizer state,
    computes gradients on its minibatch slice, applies the group's
    averaged gradients. All shards apply IDENTICAL averaged updates, so
    params and optimizer states stay synchronized without a broadcast
    per step."""

    def __init__(self, policy, clip, vf_coeff, ent_coeff, lr):
        import jax
        import optax
        self.policy = policy
        loss_fn = make_ppo_loss(policy, clip, vf_coeff, ent_coeff)
        self._optimizer = optax.adam(lr)
        self.opt_state = self._optimizer.init(policy.params)

        def grads(params, mb):
            (loss, metrics), g = jax.value_and_grad(
                loss_fn, has_aux=True)(params, mb)
            metrics["total_loss"] = loss
            return g, metrics

        def apply(params, opt_state, g):
            updates, opt_state = self._optimizer.update(g, opt_state,
                                                        params)
            import optax as _optax
            return _optax.apply_updates(params, updates), opt_state

        self._grads_jit = jax.jit(grads)
        self._apply_jit = jax.jit(apply)

    def compute_gradients(self, mb):
        import jax
        import jax.numpy as jnp
        device_mb = {k: jnp.asarray(v) for k, v in mb.items()}
        g, metrics = self._grads_jit(self.policy.params, device_mb)
        import numpy as _np
        return (jax.tree.map(_np.asarray, g),
                {k: float(v) for k, v in metrics.items()})

    def apply_gradients(self, g):
        self.policy.params, self.opt_state = self._apply_jit(
            self.policy.params, self.opt_state, g)
        return True

    def get_params(self):
        import jax
        import numpy as _np
        return jax.tree.map(_np.asarray, self.policy.params)


class PPO(Algorithm):
    _default_config_class = PPOConfig
    _supports_multi_agent = True

    def _build_update(self, policy, config: PPOConfig):
        """One jitted clipped-surrogate update bound to ``policy``
        (multi-agent builds one per policy in the map)."""
        import jax
        import jax.numpy as jnp
        import optax

        optimizer = optax.adam(config.lr)
        opt_state = optimizer.init(policy.params)
        loss_fn = make_ppo_loss(policy, config.clip_param,
                                config.vf_loss_coeff,
                                config.entropy_coeff)

        def update(params, opt_state, mb):
            (loss, metrics), grads = jax.value_and_grad(
                loss_fn, has_aux=True)(params, mb)
            updates, opt_state = optimizer.update(grads, opt_state, params)
            params = optax.apply_updates(params, updates)
            metrics["total_loss"] = loss
            return params, opt_state, metrics

        backend = getattr(config, "learner_backend", None)
        if not backend:
            # Process-default (CPU) learner: per-minibatch dispatch.
            # XLA:CPU serializes intra-op threading inside while/scan
            # bodies, so the fused program below is a ~8x PESSIMIZATION
            # there — fusion pays only on an accelerator backend.
            return jax.jit(update), opt_state

        def run_epochs(params, opt_state, batch, perm):
            """The WHOLE minibatch-SGD schedule as one program: scan
            over [epochs*minibatches] permutation rows. One dispatch
            and one host->device batch transfer per iteration instead
            of one per minibatch — rollouts stay on host CPUs while
            this jits onto the chip (the reference's CPU-rollout/
            GPU-learner split as two jax backends in one process)."""
            def one(carry, idx):
                params, opt_state = carry
                mb = jax.tree.map(lambda a: a[idx], batch)
                params, opt_state, metrics = update(params, opt_state,
                                                    mb)
                return (params, opt_state), metrics

            (params, opt_state), metrics = jax.lax.scan(
                one, (params, opt_state), perm)
            last = jax.tree.map(lambda m: m[-1], metrics)
            # Params ALSO return as one flat vector: the host pulls one
            # array instead of one transfer per leaf (each device-to-host
            # copy has a fixed cost, not just a per-byte one).
            flat = jnp.concatenate(
                [jnp.ravel(x) for x in jax.tree.leaves(params)])
            return flat, opt_state, last

        return jax.jit(run_epochs, backend=backend), opt_state

    def setup(self, config: PPOConfig) -> None:
        self._learner_shards = None
        if self.is_multi_agent:
            self._updates = {}
            self._opt_states = {}
            for pid, policy in self.local_policies.items():
                self._updates[pid], self._opt_states[pid] = \
                    self._build_update(policy, config)
            return
        n = int(getattr(config, "num_learners", 0) or 0)
        if n > 0:
            # Group mode: the shards own the optimizer states; building
            # the solo update too would allocate a dead moment tree and
            # leave self._opt_state silently diverging from the truth.
            self._update_jit = self._opt_state = None
            import ray_tpu
            shard_cls = ray_tpu.remote(_PPOGradShard)
            self._learner_shards = [
                shard_cls.remote(self.local_policy, config.clip_param,
                                 config.vf_loss_coeff,
                                 config.entropy_coeff, config.lr)
                for _ in range(n)]
            return
        self._update_jit, self._opt_state = self._build_update(
            self.local_policy, config)

    def _sgd(self, policy, update_jit, opt_state, batch: SampleBatch,
             config: PPOConfig) -> tuple:
        """Minibatch-SGD a policy on its (GAE-complete) batch; returns
        (opt_state, metrics). With learner_backend set, runs the fused
        run_epochs program on that device; otherwise per-minibatch
        dispatch on the process default."""
        import jax
        import jax.numpy as jnp
        adv = batch[SampleBatch.ADVANTAGES]
        adv = (adv - adv.mean()) / max(adv.std(), 1e-6)
        backend = getattr(config, "learner_backend", None)
        if not backend:
            sb = SampleBatch({
                "obs": batch[SampleBatch.OBS].astype(np.float32),
                "actions": batch[SampleBatch.ACTIONS],
                "old_logp":
                    batch[SampleBatch.ACTION_LOGP].astype(np.float32),
                "advantages": adv.astype(np.float32),
                "value_targets":
                    batch[SampleBatch.VALUE_TARGETS].astype(np.float32),
            })
            params = policy.params
            last_metrics: Dict[str, Any] = {}
            mb_size = min(config.sgd_minibatch_size, len(sb))
            for epoch in range(config.num_sgd_iter):
                for mb in sb.minibatches(mb_size, seed=epoch):
                    device_mb = {k: jnp.asarray(v)
                                 for k, v in mb.items()}
                    params, opt_state, metrics = update_jit(
                        params, opt_state, device_mb)
                    last_metrics = metrics
            policy.params = params
            return opt_state, {k: float(v)
                               for k, v in last_metrics.items()}

        # Fused path: each epoch permutes rows and covers floor(n/mb)
        # minibatches (the remainder rotates between epochs through the
        # permutation, matching the reference's drop-to-multiple).
        n = len(batch)
        mb_size = min(config.sgd_minibatch_size, n)
        n_mb = max(n // mb_size, 1)
        rng = np.random.default_rng(self.iteration)
        perm = np.stack([
            rng.permutation(n)[:n_mb * mb_size].reshape(n_mb, mb_size)
            for _ in range(config.num_sgd_iter)]).reshape(
                -1, mb_size).astype(np.int32)
        learner_dev = jax.devices(backend)[0]

        def put(a):
            # device_put from a NUMPY array is one host-to-device copy;
            # a committed cpu-jax array would first go through the
            # cross-backend device-to-device path.
            return jax.device_put(np.asarray(a), learner_dev)

        device_batch = {
            "obs": put(np.asarray(batch[SampleBatch.OBS], np.float32)),
            "actions": put(np.asarray(batch[SampleBatch.ACTIONS])),
            "old_logp": put(np.asarray(
                batch[SampleBatch.ACTION_LOGP], np.float32)),
            "advantages": put(adv.astype(np.float32)),
            "value_targets": put(np.asarray(
                batch[SampleBatch.VALUE_TARGETS], np.float32)),
        }
        import jax.tree_util as jtu
        params = policy.params
        leaves, treedef = jtu.tree_flatten(params)
        shapes = [np.shape(x) for x in leaves]
        params_dev = jax.device_put(params, learner_dev)
        opt_state = jax.device_put(opt_state, learner_dev)
        flat, opt_state, metrics = update_jit(
            params_dev, opt_state, device_batch, put(perm))
        # One pull, then split host-side: worker weight sync and the
        # driver's cpu-jitted evaluation path get HOST arrays without
        # one device-to-host copy per leaf.
        flat_np = np.asarray(flat)
        out, off = [], 0
        for shp in shapes:
            size = int(np.prod(shp)) if shp else 1
            out.append(flat_np[off:off + size].reshape(shp))
            off += size
        policy.params = jtu.tree_unflatten(treedef, out)
        return opt_state, {k: float(v) for k, v in metrics.items()}

    def _sgd_group(self, batch: SampleBatch, config: PPOConfig) -> dict:
        """Minibatch SGD over the learner group (num_learners > 0):
        every minibatch splits row-wise across the shard actors, their
        gradients average row-weighted (exactly the full-minibatch
        gradient — the PPO loss is mean-based), and every shard applies
        the same averaged update. Reference:
        rllib/core/rl_trainer/trainer_runner.py +
        rllib/execution/multi_gpu_learner_thread.py."""
        import jax
        import jax.numpy as jnp

        import ray_tpu
        adv = batch[SampleBatch.ADVANTAGES]
        adv = (adv - adv.mean()) / max(adv.std(), 1e-6)
        sb = SampleBatch({
            "obs": batch[SampleBatch.OBS].astype(np.float32),
            "actions": batch[SampleBatch.ACTIONS],
            "old_logp":
                batch[SampleBatch.ACTION_LOGP].astype(np.float32),
            "advantages": adv.astype(np.float32),
            "value_targets":
                batch[SampleBatch.VALUE_TARGETS].astype(np.float32),
        })
        shards = self._learner_shards
        mb_size = min(config.sgd_minibatch_size, len(sb))
        last_metrics: Dict[str, Any] = {}
        for epoch in range(config.num_sgd_iter):
            for mb in sb.minibatches(mb_size, seed=epoch):
                size = len(next(iter(mb.values())))
                n = min(len(shards), size)
                bounds = np.array_split(np.arange(size), n)
                slices = [
                    {k: np.asarray(v)[idx[0]:idx[-1] + 1]
                     for k, v in mb.items()} for idx in bounds]
                results = ray_tpu.get([
                    s.compute_gradients.remote(sl)
                    for s, sl in zip(shards, slices)])
                w = np.asarray([len(idx) / size for idx in bounds],
                               np.float64)
                avg = jax.tree.map(
                    lambda *g: np.tensordot(
                        w, np.stack(g), axes=1).astype(
                            np.asarray(g[0]).dtype),
                    *[g for g, _m in results])
                ray_tpu.get([s.apply_gradients.remote(avg)
                             for s in shards])
                metrics_list = [m for _g, m in results]
                last_metrics = {
                    k: float(np.dot(w, [m[k] for m in metrics_list]))
                    for k in metrics_list[0]}
        # Shard params stay synchronized (identical updates); pull once
        # for the driver's rollout policy.
        self.local_policy.params = jax.tree.map(
            jnp.asarray, ray_tpu.get(shards[0].get_params.remote()))
        return last_metrics

    def training_step(self) -> Dict[str, Any]:
        import ray_tpu
        config: PPOConfig = self.config
        if self.external_input is None:
            weights_ref = ray_tpu.put(self.get_weights())
            self.workers.sync_weights(weights_ref)
            per_worker = max(
                config.train_batch_size // self.workers.num_workers(), 1)
        else:
            per_worker = config.train_batch_size
        batch = self._sample_batch(per_worker)
        self._timesteps_total += len(batch)

        if self.is_multi_agent:
            out: Dict[str, Any] = {}
            for pid, sub in batch.policy_batches.items():
                self._opt_states[pid], metrics = self._sgd(
                    self.local_policies[pid], self._updates[pid],
                    self._opt_states[pid], sub, config)
                for k, v in metrics.items():
                    out[f"{pid}/{k}"] = v
            out["agent_steps_this_iter"] = batch.agent_steps()
            return out
        if self._learner_shards is not None:
            return self._sgd_group(batch, config)
        self._opt_state, metrics = self._sgd(
            self.local_policy, self._update_jit, self._opt_state, batch,
            config)
        return metrics
