"""Production training launcher: mesh + sharded params + fault-tolerant
supervisor loop. On real TPU pods, run one process per host; on CPU this
drives the same code path with a 1-device mesh.

On a TPU the step computes in bfloat16 and attention runs on the Pallas
flash kernel (SSD layers on the SSD kernel); elsewhere it computes in
float32 on the jnp reference paths. The step is compiled before the
supervisor loop starts, so a compile error fails the launch at once instead
of entering the rollback-and-retry path.

    PYTHONPATH=src python -m repro.launch.train --arch smollm-135m \
        --steps 300 --batch 8 --seq 256 --ckpt-dir /tmp/ckpt
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.compile_cache import configure_compile_cache
from repro.configs import get_config, reduced_config
from repro.dist import sharding as sh
from repro.dist.fault import TrainSupervisor
from repro.launch.mesh import make_mesh_shape
from repro.models import model as M
from repro.models.runtime import Runtime
from repro.train.data import MarkovLMDataset
from repro.train.optimizer import AdamWConfig, init_opt_state
from repro.train.train_step import make_train_step


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="smollm-135m")
    ap.add_argument("--reduced", action="store_true",
                    help="tiny same-family config (CI/demo)")
    ap.add_argument("--steps", type=int, default=300)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=256)
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--lr", type=float, default=3e-3)
    ap.add_argument("--ckpt-dir", default="/tmp/repro_ckpt")
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--data", type=int, default=1,
                    help="mesh data axis (1 on single device)")
    ap.add_argument("--model", type=int, default=1)
    ap.add_argument("--log-every", type=int, default=10)
    ap.add_argument("--fail-at", default="",
                    help="comma-separated step indices at which to inject a "
                         "node failure (fault-tolerance demo/smoke test)")
    args = ap.parse_args(argv)
    configure_compile_cache()
    try:
        fail_at = {int(s) for s in args.fail_at.split(",") if s.strip()}
    except ValueError:
        ap.error(f"--fail-at expects comma-separated step indices, "
                 f"got {args.fail_at!r}")
    bad = {s for s in fail_at if not 0 <= s < args.steps}
    if bad:
        ap.error(f"--fail-at steps {sorted(bad)} outside [0, {args.steps}): "
                 "the injected failure would never fire")

    cfg = reduced_config(args.arch) if args.reduced else get_config(args.arch)
    if jax.process_count() > 1:
        # per-host batch assembly (MarkovLMDataset host_id/num_hosts +
        # make_array_from_process_local_data) and multi-writer checkpointing
        # are not wired up yet; fail loudly rather than train on broken
        # multi-process state
        raise SystemExit("multi-process launch is not supported yet: run "
                         "one process with all local devices")
    mesh = make_mesh_shape((args.data, args.model), ("data", "model"))
    on_tpu = jax.default_backend() == "tpu"
    rt = Runtime(compute_dtype=jnp.bfloat16 if on_tpu else jnp.float32,
                 use_pallas=on_tpu,
                 remat="none" if args.reduced else "block",
                 mesh_axes={a: int(mesh.shape[a]) for a in mesh.axis_names}
                 if args.data * args.model > 1 else None)
    opt = AdamWConfig(peak_lr=args.lr, warmup_steps=max(args.steps // 20, 5),
                      total_steps=args.steps)
    ds = MarkovLMDataset(vocab=cfg.vocab, seq_len=args.seq, batch=args.batch,
                         seed=0)
    print(f"[train] {cfg.name}: {cfg.param_count()/1e6:.1f}M params, "
          f"{args.steps} steps, batch {args.batch}x{args.seq}, "
          f"entropy floor ~{ds.conditional_entropy():.3f} nats")

    rng = jax.random.PRNGKey(0)

    def init_state():
        params = M.init_params(rng, cfg)
        return params, init_opt_state(params)

    step_raw = make_train_step(cfg, rt, opt, microbatches=args.microbatches)
    with jax.set_mesh(mesh):
        state_sds = jax.eval_shape(init_state)
        p_spec = sh.param_specs(mesh, state_sds[0])
        p_shard = sh.to_shardings(mesh, p_spec)
        o_shard = sh.to_shardings(
            mesh, sh.opt_state_specs(mesh, None, p_spec))
        batch_sds = jax.tree.map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), ds.batch_at(0))
        b_shard = sh.to_shardings(mesh, sh.batch_specs(mesh, batch_sds))
        # state is created directly in its sharded layout, never whole on
        # one device
        init_fn = jax.jit(init_state, out_shardings=(p_shard, o_shard))
        # out_shardings must pin params/opt to the same layout as the inputs:
        # the step's outputs are fed straight back in (donated), and GSPMD
        # would otherwise pick its own output layout and reject the next call
        t0 = time.time()
        step_fn = jax.jit(step_raw, in_shardings=(p_shard, o_shard, b_shard),
                          out_shardings=(p_shard, o_shard, None),
                          donate_argnums=(0, 1)
                          ).lower(*state_sds, batch_sds).compile()
        print(f"[train] set-up: step compiled in {time.time() - t0:.1f}s "
              f"on {jax.devices()[0].device_kind} (use_pallas="
              f"{rt.use_pallas}, compute {jnp.dtype(rt.compute_dtype).name})",
              flush=True)

        t_start = time.time()
        last = {"t": t_start, "step": 0, "seen": 0}

        def batches(step):
            return jax.device_put(ds.batch_at(step), b_shard)

        def step_logged(params, opt_state, batch):
            t_before = time.time()
            params, opt_state, m = step_fn(params, opt_state, batch)
            s = int(opt_state["step"])
            if last["seen"] == 0:       # first step this process — may be a
                # cross-process resume at step N; window starts at this
                # step, not at process start (restore time is not tok/s)
                last["t"], last["step"] = t_before, s - 1
            elif s <= last["seen"]:     # supervisor rolled back and re-ran
                # window restarts after this step: its tokens aren't counted
                # (last["step"] = s), so its time mustn't be either
                last["t"], last["step"] = time.time(), s
            last["seen"] = s
            if s % args.log_every == 0:
                dt = time.time() - last["t"]
                tps = (s - last["step"]) * args.batch * args.seq / max(dt, 1e-9)
                print(f"  step {s:5d} loss {float(m['loss']):.4f} "
                      f"lr {float(m['lr']):.2e} gnorm "
                      f"{float(m['grad_norm']):.2f} tok/s {tps:.0f}",
                      flush=True)
                last["t"], last["step"] = time.time(), s
            return params, opt_state, m

        def injector(step):
            if step in fail_at:
                fail_at.discard(step)
                print(f"  [fault] injected failure before step {step}; "
                      "rolling back to latest checkpoint (fresh init if "
                      "none)", flush=True)
                return True
            return False

        sup = TrainSupervisor(ckpt_dir=args.ckpt_dir,
                              ckpt_every=args.ckpt_every,
                              run_tag=cfg.name,
                              shardings=(p_shard, o_shard))
        out = sup.run(init_fn, step_logged, batches, total_steps=args.steps,
                      failure_injector=injector if fail_at else None)
    final = (f"final loss {out['metrics'][-1]['loss']:.4f}" if out["metrics"]
             else "already complete (resumed at final checkpoint)")
    print(f"[train] done in {time.time()-t_start:.0f}s; {final}; "
          f"restarts {out['restarts']}; slow steps {out['slow_steps']}")
    out["runtime"] = rt
    return out


if __name__ == "__main__":
    main()
