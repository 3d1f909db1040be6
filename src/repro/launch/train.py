"""Training launcher.

CPU-scale entry point exercising the full production stack — merged adaptive
engine (QAT across profiles), AdamW, deterministic data, checkpoint/restart,
straggler monitoring. On a real TPU fleet the same step function is jitted
with the shardings from ``launch/sharding.py`` over ``make_production_mesh()``
(exactly what ``dryrun.py`` lowers); here the default is the reduced smoke
config so the driver runs end-to-end in CI.

  PYTHONPATH=src python -m repro.launch.train --arch granite-3-2b \
      --steps 50 --ckpt-dir /tmp/ckpt [--full] [--profile A8-W8] \
      [--grad-compression]
"""
from __future__ import annotations

import argparse

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import ARCHS, get_config, get_smoke
from repro.core.profiles import paper_profiles
from repro.core.engine import AdaptiveEngine, QuantIndex
from repro.data.tokens import TokenStream
from repro.models import transformer as T
from repro.optim.adam import AdamConfig
from repro.optim.compression import (compress_tree, decompress_tree,
                                     init_error_feedback)
from repro.runtime import enable_compile_cache
from repro.train.loop import TrainConfig, train


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="granite-3-2b", choices=ARCHS)
    ap.add_argument("--full", action="store_true",
                    help="use the full published config (TPU-scale)")
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--lr", type=float, default=1e-3)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--profile", default=None,
                    help="train a single profile (default: rotate all, joint QAT)")
    ap.add_argument("--grad-compression", action="store_true",
                    help="int8 gradient compression with error feedback")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    enable_compile_cache()

    cfg = get_config(args.arch) if args.full else get_smoke(args.arch)
    if cfg.frontend is not None:
        raise SystemExit("token-LM driver: pick a text arch "
                         "(audio/vlm archs train via tests/benchmarks)")
    key = jax.random.PRNGKey(args.seed)
    params = T.init_params(cfg, key)
    print(f"[train] {cfg.name}: {T.param_count(params)/1e6:.1f}M params")

    names = T.quant_layer_names(cfg)
    lo, hi = cfg.n_layers // 3, 2 * cfg.n_layers // 3
    inner = [n for n in names
             if n.startswith("L") and lo <= int(n[1:].split(".")[0]) < hi]
    profs = paper_profiles(names, inner_layers=inner)
    engine = AdaptiveEngine(tuple(profs), QuantIndex(names),
                            lambda p, br, b: T.train_loss(p, cfg, br, b))
    pid_fixed = engine.profile_id(args.profile) if args.profile else None

    stream = TokenStream(vocab=cfg.vocab, seq_len=args.seq, batch=args.batch,
                         seed=args.seed)
    ef = {"state": init_error_feedback(params) if args.grad_compression else None}

    def loss_fn(params, batch):
        pid = batch["profile_id"]
        return engine(params, pid, {"tokens": batch["tokens"],
                                    "labels": batch["labels"]})

    def data_at(step):
        b = stream.batch_at(step)
        pid = pid_fixed if pid_fixed is not None else step % len(profs)
        return {"tokens": jnp.asarray(b["tokens"]),
                "labels": jnp.asarray(b["labels"]),
                "profile_id": jnp.asarray(pid, jnp.int32)}

    step_factory = None
    if args.grad_compression:
        # compress→decompress grads around the optimizer: the int8 wire format
        # the multi-pod all-reduce uses (EF numerics shown single-host)
        from repro.optim.adam import adam_update

        def step_factory(loss_fn_, acfg_):
            def step(params, opt, ef_state, batch):
                (l, m), g = jax.value_and_grad(loss_fn_, has_aux=True)(params, batch)
                q, s, ef_state = compress_tree(g, ef_state,
                                               jax.random.PRNGKey(0))
                g = decompress_tree(q, s)
                params, opt, om = adam_update(acfg_, g, opt, params)
                return params, opt, ef_state, {"loss": l, **m, **om}

            jitted = jax.jit(step)

            def wrapped(params, opt, batch):  # loop-compatible signature
                params, opt, ef["state"], metrics = jitted(
                    params, opt, ef["state"], batch)
                return params, opt, metrics
            return wrapped
        step_transform = lambda f: f  # already jitted inside
    else:
        step_transform = jax.jit
    out = train(params, loss_fn, data_at,
                TrainConfig(steps=args.steps, ckpt_dir=args.ckpt_dir,
                            ckpt_every=max(1, args.steps // 4), log_every=5),
                AdamConfig(lr=args.lr, total_steps=args.steps, warmup_steps=10),
                step_transform=step_transform, step_factory=step_factory)
    h = out["history"]
    print(f"[train] done: loss {h[0]:.3f} → {h[-1]:.3f} "
          f"({len(h)} steps, {len(out['stragglers'])} stragglers flagged)")


if __name__ == "__main__":
    main()
