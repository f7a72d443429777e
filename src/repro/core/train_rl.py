"""On-device RL training for SDQN / SDQN-n (and supervised training for the
LSTM/Transformer baselines).

The whole loop — environment stepping, afterstate scoring, epsilon-greedy
action selection, reward shaping (Tables 3/5), replay, and the Adam/MSE
learner (Table 4) — is one XLA program: ``lax.scan`` over pod arrivals inside
``lax.scan`` over episodes, ``vmap``-ed over ``n_envs`` parallel simulated
clusters.  The actual sharded topology (the Anakin/Podracer pattern):

  * ``train(..., mesh=...)`` pins the ``n_envs`` environment batch to the
    mesh ``data`` axis with ``NamedSharding`` constraints — each device
    steps its slice of the clusters (the per-env transition and bootstrap
    scoring run under ``shard_map``, since the compiler cannot partition a
    Pallas kernel), the replay write and the (replicated) learner update
    are the only cross-device points, and XLA inserts the one all-gather
    they need.  ``mesh=None`` (or an ``n_envs`` that does
    not divide the ``data`` axis) falls back to the single-device program
    unchanged, so CPU tests and the 1-device container run the same code.
  * ``repro.train.engine.train_seeds`` vmaps this whole program over the
    seed ladder (``fold_in(key, seed)``), so ``train_and_select``'s
    candidates compile once and run as ONE launch; on a mesh
    ``launch.mesh.plan_seed_env_layout`` shards the joint (seed, env) batch
    over a 2-D ``("seed", "data")`` grid — whole replicas per device group,
    envs split inside each group — so all devices stay busy even when
    ``n_seeds`` alone is smaller than the device count.
  * In-loop afterstate scoring routes through
    ``schedulers.score_afterstates`` — the same fused-kernel dispatch the
    serving path uses (Pallas on TPU at fleet scale, where the (N, 6)
    feature matrix never hits HBM); the replay stores the single realized
    (6,) afterstate via ``env.hypothetical_place_one``.
  * The ``TrainCarry`` (fused replay ring of cap x 8 floats, Adam moments,
    params) is donated across ``train_mixture`` segments: buffers are
    updated in place at scenario hand-offs, not copied.

The default is full DQN semantics (the paper builds SDQN "on the Deep
Q-Network framework"): targets r + γ·max Q_target(s′) with a periodically
refreshed target network.  ``bootstrap=False`` recovers the literal Table-4
"target rewards" (contextual-bandit) update for ablation.
"""
from __future__ import annotations

import dataclasses
import itertools
from typing import Callable, NamedTuple, Tuple

import jax
import jax.numpy as jnp

from repro.core import dqn, env as kenv, policy as policy_mod, rewards, \
    schedulers
from repro.core.replay import Replay, replay_add, replay_init, replay_sample
from repro.core.schedulers import masked_argmax
from repro.core.types import EnvConfig

# Rewards are ~100-point scale (Table 3 base = 100); scale them down so the
# bootstrapped Q (~ r/(1-gamma)) stays O(1-10) under Adam(1e-3) + MSE.
REWARD_SCALE = 0.01


@dataclasses.dataclass(frozen=True)
class RLConfig:
    variant: str = "sdqn"          # "sdqn" | "sdqn_n"
    consolidation_n: int = 2       # the paper's n (n=2)
    episodes: int = 60
    pods_per_episode: int = 50
    n_envs: int = 8                # parallel simulated clusters
    buffer_capacity: int = 4096
    batch_size: int = 128
    eps_start: float = 0.5
    eps_end: float = 0.02
    learn_every: int = 1
    # DQN bootstrapping (the paper builds on "the Deep Q-Network framework",
    # so r + gamma*max Q(s') targets are the default; bandit=False recovers
    # the literal Table-4 "target rewards" update)
    bootstrap: bool = True
    gamma: float = 0.9
    target_update_every: int = 200
    # reward mode: efficiency_weight > 0 adds the paper's objective (minimize
    # cluster-average CPU) as a shaping term; 0 = literal Table 3/5 ablation.
    efficiency_weight: float = 10.0
    # green-consolidation shaping: points paid per node a placement newly
    # activates (rewards.energy_term); 0 = off.  Pair with churn scenarios so
    # the policy sees nodes actually emptying out over an episode.
    energy_weight: float = 0.0
    # policy class (core.policy registry): "mlp" is the paper's Table-4 net
    # and reproduces the pre-registry trainer bit-for-bit; "attention" /
    # "mamba" train through the identical loop (sequence specs thread their
    # arrival-history carry through the scanned episode and store wider
    # [afterstate | embed] replay rows).
    policy: str = "mlp"


class TrainCarry(NamedTuple):
    params: dict
    opt_state: dict
    target_params: dict
    buffer: Replay
    key: jax.Array
    learn_step: jnp.ndarray


def realized_transition(env_state, pod, action, env_cfg: EnvConfig,
                        reward_fn):
    """The action-agnostic transition body: bind a REALIZED action, shape the
    reward, build the stored replay row.

    Returns (new_env_state, stored_feats (6,), scaled reward).  Shared by the
    training loops (which pick ``action`` via a selector) and the serving
    daemon's online recorder (``sched.online.TransitionRecorder``, which
    replays the daemon's committed decisions) — using one body is what makes
    the online ring stream bit-identical to the offline one.

    action == NO_NODE (drop): there is no realized afterstate — the gather
    is clamped (a negative index would wrap to the LAST node's features) and
    the caller must zero-weight the stored transition.
    """
    before_feats = kenv.features(env_state, env_cfg)
    ok = kenv.feasible(env_state, pod, env_cfg)
    new_state = kenv.place(env_state, action, pod, env_cfg)
    after_feats = kenv.features(new_state, env_cfg)
    r = reward_fn(after_feats, before_feats, ok, action,
                  env_state.exp_pods, new_state.exp_pods)
    # only the realized afterstate is stored: a single row, never the (N, 6)
    # matrix (any scoring pass that picked `action` goes through the fused
    # kernel dispatch and does not materialize it either)
    stored = kenv.normalize_features(
        kenv.hypothetical_place_one(env_state, pod, env_cfg,
                                    jnp.maximum(action, 0)))
    return new_state, stored, r * REWARD_SCALE


def transition_step(key, select, env_state, pod, dt_s, env_cfg: EnvConfig,
                    reward_fn):
    """One pod arrival in one env, shared by the RL and supervised loops:
    act via ``select``, bind, shape the reward, advance wall-clock.

    Returns (new_env_state, stored_feats (6,), scaled reward, action).
    ``select(key, state, pod) -> node`` is any episode-compatible selector
    (epsilon-greedy SDQN for RL, ``kube_select`` for behavior cloning);
    ``reward_fn`` follows the ``rewards.make_reward_fn`` interface.
    """
    action = select(key, env_state, pod)
    new_state, stored, r = realized_transition(env_state, pod, action,
                                               env_cfg, reward_fn)
    new_state = kenv.tick(new_state, env_cfg, dt_s)
    return new_state, stored, r, action


def _transition(key, qparams, env_state, pod, dt_s, env_cfg: EnvConfig,
                epsilon, reward_fn, spec=None, embed=None):
    """One RL pod arrival: epsilon-greedy over ``schedulers.score_afterstates``
    (the shared fused-kernel dispatch) + the common transition body.

    ``spec``/``embed`` route scoring through a registered policy class
    (``core.policy``); sequence specs append their history ``embed`` to the
    stored replay row.  The defaults reproduce the pre-registry MLP trainer
    exactly (pinned in tests/test_train_engine.py).
    """

    def select(k, st, p):
        ok = kenv.feasible(st, p, env_cfg)
        q = schedulers.score_afterstates(qparams, st, p, env_cfg,
                                         policy=spec, embed=embed)
        return masked_argmax(k, q, ok, epsilon)

    new_state, stored, r, action = transition_step(
        key, select, env_state, pod, dt_s, env_cfg, reward_fn)
    if embed is not None:
        stored = jnp.concatenate([stored, embed])
    return new_state, stored, r, action


def _bootstrap_bonus(online_params, target_params, env_state, pod, env_cfg,
                     rl: RLConfig, spec=None, embed=None):
    """Double-DQN bonus: gamma * Q_target(s', argmax_a Q_online(s', a)).

    0 when s' has no feasible action (terminal for this workload burst).
    Double-DQN (action chosen by the online net, valued by the target net)
    avoids the max-operator over-estimation of rarely-visited states — e.g.
    cold-pull afterstates that look mid-band attractive.  Scoring goes
    through the fused dispatch; only the argmax afterstate is gathered for
    the target net (one (6,) row, not the (N, 6) matrix).  For sequence
    policy classes ``embed`` is the history embedding AT the next arrival
    (the online carry stepped by the next pod's workload), appended to the
    target row exactly as stored transitions are.
    """
    ok = kenv.feasible(env_state, pod, env_cfg)
    q_online = schedulers.score_afterstates(online_params, env_state, pod,
                                            env_cfg, policy=spec, embed=embed)
    a_star = jnp.argmax(jnp.where(ok, q_online, -jnp.inf))
    after_star = kenv.normalize_features(
        kenv.hypothetical_place_one(env_state, pod, env_cfg, a_star))
    if embed is not None:
        after_star = jnp.concatenate([after_star, embed])
    qfn = dqn.qvalues if spec is None else spec.qvalues
    q_tgt = qfn(target_params, after_star)
    return jnp.where(jnp.any(ok), rl.gamma * q_tgt, 0.0)


def _env_sharded(mesh, n_envs: int) -> bool:
    return (mesh is not None and "data" in mesh.axis_names
            and n_envs % mesh.shape["data"] == 0)


def _env_constraint(mesh, n_envs: int):
    """Sharding-constraint applier for env-batched pytrees, or identity.

    With a mesh whose ``data`` axis divides ``n_envs``, pins the environment
    batch dimension to ``data`` (``NamedSharding``); the learner stays
    replicated, which is exactly the Anakin/Podracer layout.  Any other case
    (``mesh=None``, no ``data`` axis, indivisible batch) returns identity so
    the single-device program is untouched.
    """
    if not _env_sharded(mesh, n_envs):
        return lambda tree, time_leading=False: tree
    from jax.sharding import NamedSharding, PartitionSpec as P

    def constrain(tree, time_leading=False):
        spec = P(None, "data") if time_leading else P("data")
        return jax.lax.with_sharding_constraint(tree, NamedSharding(mesh, spec))

    return constrain


def _env_map(mesh, n_envs: int):
    """``env_map(fn, batched, shared)``: ``fn(*batched[i], *shared)`` for
    every env ``i``, stacked.

    A ``vmap`` over the env axis.  When ``mesh`` shards envs over ``data``
    (``_env_constraint``) it runs under ``shard_map``, each device mapping
    its own envs: the per-env scoring holds Pallas kernels, which the
    compiler cannot partition.  ``shared`` (params, scalars) is replicated.
    """
    sharded = _env_sharded(mesh, n_envs)

    def env_map(fn, batched, shared=()):
        def mapped(b, s):
            return jax.vmap(lambda *xs: fn(*xs, *s))(*b)

        if sharded:
            P = jax.sharding.PartitionSpec
            # check_vma=False: pallas_call's outputs carry no varying-axes
            # type, and under train_seeds' vmap(spmd_axis_name="seed") the
            # check also rejects the batched seed axis
            mapped = jax.shard_map(mapped, mesh=mesh,
                                   in_specs=(P("data"), P()),
                                   out_specs=P("data"), check_vma=False)
        return mapped(tuple(batched), tuple(shared))

    return env_map


def _make_episode_fn(env_cfg: EnvConfig, rl: RLConfig, n_steps_total: int,
                     mesh=None):
    """Episode body for ``lax.scan``: (TrainCarry, global episode idx) -> carry.

    Per-arrival ``PodSpec``s come from the scenario's pod table (the
    homogeneous default pod when ``env_cfg.scenario`` is None), so the same
    Q-net trains across heterogeneous workload mixtures.  ``n_steps_total``
    anchors the epsilon schedule, which lets scenario-mixture training thread
    one schedule through interleaved per-scenario segments.  ``mesh`` shards
    the ``n_envs`` batch over the ``data`` axis (see ``_env_constraint``).
    """
    reward_fn = rewards.make_reward_fn(rl.variant, rl.consolidation_n,
                                       rl.efficiency_weight, rl.energy_weight)
    shard = _env_constraint(mesh, rl.n_envs)
    env_map = _env_map(mesh, rl.n_envs)
    spec = policy_mod.get(rl.policy)
    # Python-level static: sequence specs (embed_dim > 0) thread per-env
    # encoder carries through the pod scan; stateless specs thread an empty
    # pytree, which adds no arrays — the "mlp" trace is byte-identical to the
    # pre-registry trainer.
    seq = spec.embed_dim > 0
    step_fn = policy_mod.make_train_step(spec)

    def epsilon_at(step):
        frac = step.astype(jnp.float32) / max(n_steps_total, 1)
        return rl.eps_start + (rl.eps_end - rl.eps_start) * jnp.minimum(frac, 1.0)

    def episode(carry: TrainCarry, ep_idx):
        key_ep = jax.random.fold_in(carry.key, ep_idx)
        k_reset, k_pods, k_steps = jax.random.split(key_ep, 3)
        env_states = shard(jax.vmap(lambda k: kenv.reset(k, env_cfg))(
            jax.random.split(k_reset, rl.n_envs)
        ))
        # pre-sample each env's arrival stream; scan wants leading dim = time
        tables = jax.vmap(
            lambda k: kenv.sample_pod_table(k, env_cfg, rl.pods_per_episode)
        )(jax.random.split(k_pods, rl.n_envs))
        pods_t = shard(jax.tree.map(lambda x: jnp.swapaxes(x, 0, 1), tables.specs),
                       time_leading=True)
        dt_t = shard(jnp.swapaxes(tables.dt_s, 0, 1), time_leading=True)
        life_t = shard(jnp.swapaxes(tables.lifetime_s, 0, 1), time_leading=True)
        # the arrival after this one, for bootstrapped Q(s') scoring (the last
        # row wraps, but its bonus is masked out below)
        pods_next_t = jax.tree.map(lambda x: jnp.roll(x, -1, axis=0), pods_t)
        # per-env expiry ledgers: the training envs churn exactly like eval
        # episodes — placed pods retire mid-episode and release resources, so
        # the Q-net learns on clusters where idle nodes actually appear.
        # Skipped at trace time for all-immortal catalogs (has_lifecycle is a
        # static property): the hot loop pays for retirement scatters only
        # on churn scenarios.
        use_ledger = kenv.has_lifecycle(env_cfg)
        ledgers = jax.vmap(lambda _: kenv.ledger_init(
            rl.pods_per_episode if use_ledger else 1))(jnp.arange(rl.n_envs))
        # per-env arrival-history carries (fresh each episode, like the env
        # reset); () for stateless specs keeps the scan signature unchanged
        if seq:
            carries0 = jax.tree.map(
                lambda z: jnp.zeros((rl.n_envs,) + z.shape, z.dtype),
                spec.carry_init(carry.params))
        else:
            carries0 = ()

        def pod_step(inner, xs):
            t, pod_t, pod_next_t, dt_row, life_row = xs
            c, env_states, ledgers, carries = inner
            kt = jax.random.fold_in(k_steps, t)
            step_no = ep_idx * rl.pods_per_episode + t
            eps = epsilon_at(step_no)
            keys = jax.random.split(kt, rl.n_envs + 2)
            expiry = env_states.time_s + life_row  # pods start at bind time
            if seq:
                # advance every env's history with this arrival's workload;
                # the resulting embedding conditions both scoring and the
                # stored replay row (wide [afterstate | embed] features)
                wf = jax.vmap(policy_mod.pod_workload_features)(pod_t)
                carries, embeds = jax.vmap(
                    spec.encode_step, in_axes=(None, 0, 0)
                )(c.params, carries, wf)
                new_states, stored, r, actions = env_map(
                    lambda kk, st, pod, dt, emb, params, eps: _transition(
                        kk, params, st, pod, dt, env_cfg, eps, reward_fn,
                        spec=spec, embed=emb),
                    (keys[: rl.n_envs], env_states, pod_t, dt_row, embeds),
                    (c.params, eps))
            else:
                new_states, stored, r, actions = env_map(
                    lambda kk, st, pod, dt, params, eps: _transition(
                        kk, params, st, pod, dt, env_cfg, eps, reward_fn,
                        spec=spec),
                    (keys[: rl.n_envs], env_states, pod_t, dt_row),
                    (c.params, eps))
            if use_ledger:
                ledgers = jax.vmap(
                    lambda led, a, e, pod: kenv.ledger_record(led, t, a, e, pod)
                )(ledgers, actions, expiry, pod_t)
                new_states, ledgers, _ = jax.vmap(kenv.retire_expired)(
                    new_states, ledgers)
            new_states = shard(new_states)

            targets = r
            if rl.bootstrap:
                if seq:
                    # peek the next arrival's embedding (carry stepped but NOT
                    # committed — the real advance happens next iteration)
                    wf_next = jax.vmap(policy_mod.pod_workload_features)(
                        pod_next_t)
                    _, embeds_next = jax.vmap(
                        spec.encode_step, in_axes=(None, 0, 0)
                    )(c.params, carries, wf_next)
                    bonus = env_map(
                        lambda st, pod, emb, params, target: _bootstrap_bonus(
                            params, target, st, pod, env_cfg, rl, spec=spec,
                            embed=emb),
                        (new_states, pod_next_t, embeds_next),
                        (c.params, c.target_params))
                else:
                    bonus = env_map(
                        lambda st, pod, params, target: _bootstrap_bonus(
                            params, target, st, pod, env_cfg, rl, spec=spec),
                        (new_states, pod_next_t),
                        (c.params, c.target_params))
                targets = r + jnp.where(t + 1 < rl.pods_per_episode, bonus, 0.0)

            # dropped arrivals (all-infeasible burst) store with weight 0:
            # their features/reward describe a placement that never happened
            buf = replay_add(c.buffer, stored, targets,
                             (actions >= 0).astype(jnp.float32))
            feats_b, targets_b, w = replay_sample(buf, keys[-1], rl.batch_size)
            params_, opt_, loss, _ = step_fn(c.params, c.opt_state, feats_b, targets_b, w)

            learn_step = c.learn_step + 1
            tgt = jax.tree.map(
                lambda new, old: jnp.where(
                    learn_step % rl.target_update_every == 0, new, old
                ),
                params_,
                c.target_params,
            )
            c = TrainCarry(params_, opt_, tgt, buf, c.key, learn_step)
            return (c, new_states, ledgers, carries), (loss, jnp.mean(r))

        (carry2, env_states, _, _), (losses, rews) = jax.lax.scan(
            pod_step, (carry, env_states, ledgers, carries0),
            (jnp.arange(rl.pods_per_episode), pods_t, pods_next_t, dt_t, life_t),
        )
        metric = jax.vmap(lambda st: kenv.average_cpu_utilization(st, env_cfg))(env_states)
        return carry2, {
            "loss": losses.mean(),
            "reward": rews.mean(),
            "avg_cpu": metric.mean(),
        }

    return episode


def _init_carry(key: jax.Array, rl: RLConfig) -> TrainCarry:
    k_init, k_train = jax.random.split(key)
    spec = policy_mod.get(rl.policy)
    params, opt_state = policy_mod.init_train_state(spec, k_init)
    # lane = the env batch: every in-loop add is one whole (n_envs, F) row,
    # so the ring write is a contiguous slice update, not a scatter (replay
    # contents and sampling are identical either way — lane is layout only).
    # F = spec.feature_dim: sequence specs store [afterstate | embed] rows.
    lane = rl.n_envs if rl.buffer_capacity % rl.n_envs == 0 else 1
    buffer = replay_init(rl.buffer_capacity, n_features=spec.feature_dim,
                         lane=lane)
    # the target net starts equal to the online net but must own its buffers:
    # the TrainCarry is donated across jitted segments, and XLA refuses to
    # donate the same buffer twice
    target = jax.tree.map(jnp.copy, params)
    return TrainCarry(params, opt_state, target, buffer, k_train,
                      jnp.zeros((), jnp.int32))


def train(
    key: jax.Array,
    env_cfg: EnvConfig,
    rl: RLConfig,
    mesh=None,
) -> Tuple[dict, dict]:
    """Train SDQN/SDQN-n. Returns (qparams, metrics dict of per-episode arrays).

    ``mesh`` (e.g. ``launch.mesh.make_train_mesh()``) shards the ``n_envs``
    environment batch over the ``data`` axis; ``None`` or a 1-device mesh
    runs the identical single-device program.  For multi-candidate training
    prefer ``repro.train.engine.train_seeds``, which vmaps this whole
    function over the seed ladder in one launch.
    """
    carry = _init_carry(key, rl)
    episode = _make_episode_fn(env_cfg, rl, rl.episodes * rl.pods_per_episode,
                               mesh)
    carry, metrics = jax.lax.scan(episode, carry, jnp.arange(rl.episodes))
    return carry.params, metrics


train_jit = jax.jit(train, static_argnames=("env_cfg", "rl", "mesh"))


def train_mixture(
    key: jax.Array,
    env_cfgs,
    rl: RLConfig,
    rounds: int = 4,
    mesh=None,
) -> Tuple[dict, dict]:
    """Train ONE Q-net across a scenario mixture.

    ``rl.episodes`` is split evenly across the scenario ``EnvConfig``s and
    interleaved over ``rounds`` visits, so late training (low epsilon) still
    sees every scenario.  Params, target net, replay buffer, learn-step and
    the epsilon schedule all thread through: the replay stores (6,)-feature
    afterstates, which are node-count-independent, so transitions from a
    4-node paper cluster and a 1024-node heterogeneous fleet mix freely in
    one buffer.

    Returns (qparams, metrics dict of per-episode arrays concatenated in
    training order).  The episode budget is honored to within one chunk
    (= episodes // (len(cfgs) * rounds), min 1): scenarios are visited in
    cycle until ``rl.episodes`` episodes have run, so a budget smaller than
    one full cycle trains exactly that many episodes rather than inflating
    to a whole round.
    """
    env_cfgs = list(env_cfgs)
    chunk = max(rl.episodes // (len(env_cfgs) * rounds), 1)
    schedule = []
    total_eps = 0
    cycle = itertools.cycle(env_cfgs)
    while total_eps < rl.episodes:
        schedule.append(next(cycle))
        total_eps += chunk
    n_steps_total = total_eps * rl.pods_per_episode

    segments = {}
    for cfg in env_cfgs:
        if cfg in segments:
            continue
        ep_fn = _make_episode_fn(cfg, rl, n_steps_total, mesh)

        def _segment(carry, ep0, _episode=ep_fn):
            return jax.lax.scan(_episode, carry, ep0 + jnp.arange(chunk))

        # the TrainCarry is donated: the fused replay ring (cap x 8 floats),
        # the Adam moments and both parameter sets are updated in place at
        # every scenario hand-off instead of being copied per segment
        segments[cfg] = jax.jit(_segment, donate_argnums=(0,))

    carry = _init_carry(key, rl)
    per_ep = []
    ep0 = 0
    for cfg in schedule:
        carry, m = segments[cfg](carry, jnp.int32(ep0))
        per_ep.append(m)
        ep0 += chunk
    metrics = {
        k: jnp.concatenate([m[k] for m in per_ep]) for k in per_ep[0]
    }
    return carry.params, metrics


# ---------------------------------------------------------------------------
# supervised training for the LSTM / Transformer baselines (Tables 6/7)
# ---------------------------------------------------------------------------


def train_supervised_scorer(
    key: jax.Array,
    env_cfg: EnvConfig,
    init_fn: Callable,
    score_fn: Callable,
    episodes: int = 40,
    pods_per_episode: int = 50,
    n_envs: int = 8,
    efficiency_weight: float = 10.0,
) -> dict:
    """Train a scorer by regression onto Table-3 rewards along kube-scheduler
    trajectories (the paper trains its LSTM/Transformer on the same reward
    signal; they are behavior-cloning value estimators, not RL agents).

    The act/place/reward/clamp body is the same ``transition_step`` the RL
    loop scans — only the selector (``kube_select``) and the learner (MSE
    regression instead of Q-learning) differ.  Dropped arrivals
    (``action == NO_NODE``) zero-weight their sample exactly as in RL.
    """
    from repro.core import baselines

    params, opt_state = baselines.init_regression_state(init_fn, key)
    step_fn = baselines.make_regression_trainer(score_fn)
    pod = kenv.default_pod(env_cfg)
    select = schedulers.make_kube_selector(env_cfg)
    reward_fn = rewards.make_reward_fn("sdqn", efficiency_weight=efficiency_weight)

    def episode(carry, ep_idx):
        params, opt_state = carry
        key_ep = jax.random.fold_in(key, ep_idx)
        env_states = jax.vmap(lambda k: kenv.reset(k, env_cfg))(
            jax.random.split(key_ep, n_envs)
        )

        def pod_step(inner, t):
            (params, opt_state), env_states = inner
            kt = jax.random.split(jax.random.fold_in(key_ep, 1000 + t), n_envs)
            env_states, feats, targs, actions = jax.vmap(
                lambda k, st: transition_step(k, select, st, pod,
                                              env_cfg.schedule_dt_s, env_cfg,
                                              reward_fn)
            )(kt, env_states)
            valid = (actions >= 0).astype(jnp.float32)
            params, opt_state, loss = step_fn(params, opt_state, feats, targs, valid)
            return ((params, opt_state), env_states), loss

        ((params, opt_state), _), losses = jax.lax.scan(
            pod_step, ((params, opt_state), env_states), jnp.arange(pods_per_episode)
        )
        return (params, opt_state), losses.mean()

    (params, _), _ = jax.lax.scan(episode, (params, opt_state), jnp.arange(episodes))
    return params


# ---------------------------------------------------------------------------
# multi-seed training with validation-based selection (the paper's
# "Algorithm Selection and Scheduler Development" step: train candidate
# models, keep the one that schedules best on held-out validation bursts)
# ---------------------------------------------------------------------------


def train_and_select(
    key: jax.Array,
    train_cfg: EnvConfig,
    eval_cfg: EnvConfig,
    rl: RLConfig,
    n_seeds: int = 4,
    val_trials: int = 12,
    val_pods: int = 50,
    mesh=None,
):
    """Train `n_seeds` independent policies, return the one with the lowest
    average-CPU metric on validation episodes (seeds disjoint from the
    benchmark trials, which use PRNGKey(100+)).

    Delegates to ``repro.train.engine``: the seed dimension is vmapped over
    the whole training scan (one compilation, ONE launch for all candidates
    — the old path dispatched ``train`` per seed from Python), validation
    runs all (seed, trial) episodes batched, and the winner is a NaN-guarded
    on-device argmin (an all-NaN validation falls back to seed 0 instead of
    returning ``(None, inf)``).  The seed ladder is ``fold_in(key, s)``,
    identical to the sequential path, so the same candidate wins selection
    (per-seed params agree to float-reassociation tolerance, ~1e-9/step).
    """
    from repro.train import engine

    return engine.train_and_select(key, train_cfg, eval_cfg, rl,
                                   n_seeds=n_seeds, val_trials=val_trials,
                                   val_pods=val_pods, mesh=mesh)
