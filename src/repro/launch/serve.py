"""Batched serving driver with SDQN request routing.

Serves a small LM with continuous batching: requests arrive in waves, the
SDQN placement *daemon* (the paper's scheduler as a continuously-serving
loop, ``repro.sched.daemon``) routes each request wave to one of several
model-server replicas based on replica load features — waves are submitted
as placement requests, batch-scored in one device launch, and bound with
optimistic concurrency — then each replica runs prefill + decode.

    PYTHONPATH=src python -m repro.launch.serve --arch olmo-1b --smoke \\
        --replicas 4 --requests 64 --gen-tokens 16 \\
        --qnet-path runs/rl/ckpt     # repro.checkpoint dir (or legacy .npz)
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import get_config
from repro.launch import compile_cache
from repro.models import model as mdl
from repro.sched.daemon import DaemonConfig, FleetSubstrate, PlacementDaemon
from repro.sched.placement import JobSpec, fresh_fleet


def sample_requests(key, n, vocab, prompt_len):
    return jax.random.randint(key, (n, prompt_len), 0, vocab)


def load_policy(path: str, key: jax.Array, policy: str = "mlp"):
    """SDQN routing params + their policy class: ``(params, PolicySpec)``.

    ``path`` is a ``repro.checkpoint`` directory (the trainer's ``ckpt.save``
    layout, latest step), a legacy flat ``.npz`` (always the Table-4 MLP), or
    empty for a fresh init of ``policy``.  Checkpoint directories carry their
    policy class in the manifest (``core.policy.checkpoint_metadata``), so a
    single ``--qnet-path`` restores ANY registered variant; pre-registry
    checkpoints with no metadata fall back to ``policy``.
    """
    from repro.core import policy as policy_mod

    if not path:
        spec = policy_mod.get(policy)
        return spec.init(key), spec
    if path.endswith(".npz"):
        loaded = np.load(path)
        return ({k: jnp.asarray(loaded[k]) for k in loaded.files},
                policy_mod.get("mlp"))
    return policy_mod.restore_checkpoint(path, default_policy=policy)


def load_qnet(path: str, key: jax.Array) -> dict:
    """Legacy entry point: just the params (MLP default).  Prefer
    ``load_policy``, which also recovers the checkpoint's policy class."""
    params, _ = load_policy(path, key)
    return params


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="olmo-1b")
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--replicas", type=int, default=4)
    ap.add_argument("--requests", type=int, default=64)
    ap.add_argument("--wave-size", type=int, default=8)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--gen-tokens", type=int, default=16)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--qnet-path", default="",
                    help="trained SDQN params: repro.checkpoint dir or legacy "
                         "npz; fresh init if empty")
    ap.add_argument("--policy", default="mlp",
                    help="policy class (core.policy registry) when --qnet-path "
                         "is empty or carries no policy metadata; checkpoint "
                         "metadata wins otherwise")
    ap.add_argument("--online", action="store_true",
                    help="close the loop: record every realized routing "
                         "decision (FleetTransitionRecorder) and fine-tune "
                         "the routing policy on the realized rewards "
                         "(OnlineRefresher; params hot-swap atomically at "
                         "batch-cut boundaries)")
    ap.add_argument("--online-steps", type=int, default=4,
                    help="refresh cycles to run after the routing burst "
                         "(with --online)")
    args = ap.parse_args(argv)
    compile_cache.enable()

    cfg = get_config(args.arch, smoke=args.smoke)
    key = jax.random.PRNGKey(args.seed)
    params = mdl.init_params(key, cfg)

    max_len = args.prompt_len + args.gen_tokens

    @jax.jit
    def prefill_fn(p, tokens):
        logits, cache = mdl.prefill(p, cfg, tokens, {}, q_chunk=64)
        return logits, cache

    @jax.jit
    def decode_fn(p, tok, cache, idx):
        return mdl.decode_step(p, cfg, tok, cache, idx)

    # SDQN routing across replicas, served by the placement daemon: waves are
    # submitted as requests, batch-scored in one launch, optimistically bound
    qparams, qspec = load_policy(args.qnet_path, jax.random.fold_in(key, 1),
                                 policy=args.policy)
    fleet = fresh_fleet(args.replicas, jax.random.fold_in(key, 2))
    waves = args.requests // args.wave_size
    sub = FleetSubstrate(fleet, policy=qspec)
    recorder = None
    if args.online:
        from repro.core.types import FEATURE_DIM
        from repro.sched.online import FleetTransitionRecorder

        if qspec.feature_dim != FEATURE_DIM:
            raise SystemExit(
                f"--online needs a policy with the canonical afterstate "
                f"feature width ({FEATURE_DIM}); {qspec.name} trains on "
                f"{qspec.feature_dim}-wide rows")
        recorder = FleetTransitionRecorder(fleet)
    daemon = PlacementDaemon(
        sub, qparams,
        DaemonConfig(batch_size=max(min(waves, 8), 1), max_wait_s=0.0),
        decision_hook=recorder.record if recorder else None)
    daemon.warmup()
    job = JobSpec(cpu_pct_demand=100.0 / max(waves, 1), kind="serve")

    for _ in range(waves):
        daemon.submit(job)
    daemon.drain()
    assignments = [d.node for d in sorted(daemon.decisions)]

    if args.online:
        # after external churn (replica restarts, manual unbinds) the shadow
        # must be rebased first: recorder.resync(sub.live) — this burst is a
        # pure submit/bind trace, so a plain drain/train/publish cycle works
        from repro.sched.online import OnlineRefresher

        ref = OnlineRefresher(daemon, recorder, spec=qspec)
        ref.warmup()
        for _ in range(args.online_steps):
            ref.step()
        loss = "n/a" if ref.last_loss is None else f"{ref.last_loss:.4f}"
        print(f"[serve] online refresh: {recorder.drained} transitions "
              f"recorded, {ref.steps} refresh steps, {ref.swaps} param "
              f"swaps, last_loss={loss}")

    t0 = time.time()
    generated = 0
    for w, replica in enumerate(assignments):
        kw = jax.random.fold_in(key, 100 + w)
        prompts = sample_requests(kw, args.wave_size, cfg.vocab_size, args.prompt_len)
        logits, cache = prefill_fn(params, prompts)
        # pad the prefill cache out to max_len for decoding
        def pad(leaf):
            if leaf.ndim == 5 and leaf.shape[2] == args.prompt_len:  # (nb,B,S,H,hd)
                pad_width = [(0, 0)] * 5
                pad_width[2] = (0, args.gen_tokens)
                return jnp.pad(leaf, pad_width)
            return leaf
        cache = jax.tree.map(pad, cache)

        tok = jnp.argmax(logits, axis=-1)[:, None].astype(jnp.int32)
        out_tokens = [tok]
        for i in range(args.gen_tokens - 1):
            logits, cache = decode_fn(params, tok, cache, jnp.int32(args.prompt_len + i))
            tok = jnp.argmax(logits, axis=-1)[:, None].astype(jnp.int32)
            out_tokens.append(tok)
        generated += args.wave_size * args.gen_tokens

    dt = time.time() - t0
    placed = [a for a in assignments if a >= 0]
    counts = np.bincount(np.asarray(placed, np.int64), minlength=args.replicas)
    print(f"[serve] {args.requests} requests, {generated} tokens in {dt:.1f}s "
          f"({generated / dt:.1f} tok/s)")
    print(f"[serve] SDQN routing ({qspec.name}) across replicas: "
          f"{counts.tolist()} "
          f"({daemon.metrics.batches} daemon batches, "
          f"{daemon.metrics.device_launches} scoring launches, "
          f"{daemon.metrics.conflicts} bind conflicts)")
    print(f"[serve] replica load (cpu%): "
          f"{np.round(np.asarray(sub.live.cpu_pct), 1).tolist()}")
    return counts


if __name__ == "__main__":
    main()
