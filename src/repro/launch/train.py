"""Production training launcher.

Wires together: arch config → BitDistill student → sharding plan → pjit'd
train/distill step → fault-tolerant loop (async checkpoints, auto-resume,
SIGTERM emergency save, straggler watchdog, optional cross-pod gradient
compression).

    PYTHONPATH=src python -m repro.launch.train --arch qwen1.5-0.5b \
        --dp 2 --tp 1 --steps 200 --task sst2-syn --ckpt-dir runs/run1

--dp x --tp must not exceed the devices JAX sees: one chip takes --dp 1
--tp 1, a four-chip host --dp 4, a pod --dp 16 --tp 16.  The same entry
point is what a 1000-node deployment supervises per-host
(jax.distributed.initialize is a no-op single-host).  Without --ckpt-dir
nothing is saved or resumed.  ``main()`` returns each step's metrics
(``loss``, ``grad_norm``, ``lr``, ...) as floats.
"""
from __future__ import annotations

import argparse
import signal
import sys
import time
from typing import Dict, List

import jax
import jax.numpy as jnp
import numpy as np

from repro.checkpoint import CheckpointManager, load_checkpoint
from repro.checkpoint.ckpt import latest_step
from repro.core import quant as Q
from repro.data.loader import DataLoader
from repro.data.synth import get_task
from repro.distributed import sharding as shlib
from repro.distributed.elastic import StepWatchdog
from repro.distributed.sharding import ShardingPlan, default_rules
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import make_mesh
from repro.models import build_model
from repro.models.base import get_config
from repro.training.optimizer import AdamW, AdamWConfig
from repro.training.schedule import warmup_cosine
from repro.training.trainer import TrainState, init_train_state, make_train_step


def main(argv=None) -> List[Dict[str, float]]:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--dp", type=int, default=1)
    ap.add_argument("--tp", type=int, default=1)
    ap.add_argument("--pods", type=int, default=1)
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--lr", type=float, default=1e-4)
    ap.add_argument("--task", default="corpus")
    ap.add_argument("--ckpt-dir", default=None,
                    help="checkpoint and resume directory (default: none)")
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--reduced", action="store_true",
                    help="train the smoke-scale config (CPU-friendly)")
    ap.add_argument("--quant", default="qat", choices=["fp", "qat"])
    ap.add_argument("--log-every", type=int, default=10)
    args = ap.parse_args(argv)
    enable_compile_cache()

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    if args.quant == "qat":
        cfg = cfg.with_quant(Q.QAT)
    cfg = cfg.replace(max_seq=max(cfg.max_seq, args.seq))

    mesh = make_mesh(args.dp, args.tp, args.pods)
    plan = ShardingPlan(mesh, default_rules(args.pods > 1))
    model = build_model(cfg)
    opt = AdamW(AdamWConfig())
    lr_fn = lambda s: warmup_cosine(s, args.lr, min(20, args.steps // 10 + 1),
                                    args.steps)

    params_struct = jax.eval_shape(lambda: model.init(jax.random.PRNGKey(0)))
    p_sh = plan.tree_shardings(model.param_axes(), params_struct)
    opt_struct = jax.eval_shape(opt.init, params_struct)
    o_sh = plan.tree_shardings(opt.state_axes(model.param_axes()), opt_struct)
    from jax.sharding import NamedSharding, PartitionSpec as P
    state_sh = TrainState(p_sh, o_sh, NamedSharding(mesh, P()))
    batch_sh = {k: plan.sharding(("batch", "seq"), (args.batch, args.seq))
                for k in ("tokens", "labels", "loss_mask")}

    step_fn = jax.jit(make_train_step(model, opt, lr_fn),
                      in_shardings=(state_sh, batch_sh),
                      donate_argnums=(0,))

    loader = DataLoader(get_task(args.task), args.batch, args.seq,
                        host_id=jax.process_index(),
                        num_hosts=jax.process_count())
    loader.start_prefetch()
    mgr = (CheckpointManager(args.ckpt_dir, save_every=args.ckpt_every)
           if args.ckpt_dir else None)
    watchdog = StepWatchdog()
    history = []

    # ---- init or resume ------------------------------------------------------
    shlib.set_plan(plan)
    stop = {"now": False}

    def on_term(sig, frm):
        stop["now"] = True
    prev_term = signal.signal(signal.SIGTERM, on_term)
    try:
        with mesh:
            if mgr is not None and latest_step(args.ckpt_dir) is not None:
                tmpl = jax.eval_shape(lambda: init_train_state(
                    model.init(jax.random.PRNGKey(0)), opt))
                state, extra, start = load_checkpoint(
                    args.ckpt_dir, tmpl, shardings=state_sh)
                loader.load_state_dict(extra.get("loader", {"step": 0}))
                print(f"[resume] from step {start}")
            else:
                init_fn = jax.jit(
                    lambda k: init_train_state(model.init(k), opt),
                    out_shardings=state_sh)
                state = init_fn(jax.random.PRNGKey(0))
                start = 0

            t_start = time.time()
            for i in range(start, args.steps):
                watchdog.start()
                batch = {k: jnp.asarray(v) for k, v in loader.next().items()
                         if k in ("tokens", "labels", "loss_mask")}
                state, metrics = step_fn(state, batch)
                history.append(metrics)
                flag = watchdog.stop()
                if flag:
                    print(f"[straggler] step {flag.step}: "
                          f"{flag.duration:.3f}s (median {flag.median:.3f}s)")
                if i % args.log_every == 0:
                    m = {k: float(v) for k, v in metrics.items()}
                    print(f"step {i}  loss {m.get('loss', float('nan')):.4f}  "
                          f"lr {m.get('lr', 0):.2e}  "
                          f"({(time.time()-t_start):.1f}s)")
                if mgr is not None and mgr.should_save(i + 1):
                    mgr.save_async(i + 1, state,
                                   extra={"loader": loader.state_dict()})
                if stop["now"]:
                    if mgr is not None:
                        print("[sigterm] emergency checkpoint")
                        mgr.emergency_save(
                            i + 1, state,
                            extra={"loader": loader.state_dict()})
                    sys.exit(0)
            if mgr is not None:
                mgr.wait()
                mgr.emergency_save(args.steps, state,
                                   extra={"loader": loader.state_dict()})
    finally:
        signal.signal(signal.SIGTERM, prev_term)
        shlib.set_plan(None)
        loader.stop_prefetch()
    print("done")
    return [{k: float(v) for k, v in m.items()} for m in history]


if __name__ == "__main__":
    main()
