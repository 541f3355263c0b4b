"""End-to-end driver: train a ~100M-param MLLM with DFLOP for a few hundred
steps on synthetic mixed multimodal data, comparing the Online Microbatch
Scheduler against random (data-agnostic) assignment.

Scheduling runs through the `repro.runtime` control loop: every step's
wall time feeds back into calibration + drift detection, and `--trace`
exports a Chrome trace (load in https://ui.perfetto.dev) of the run.
`--replan` additionally lets the controller re-plan in the background and
hot-swap θ* when the data distribution drifts — and the swap is
*physical*: the live (params, opt) pytree is threaded through a
`repro.launch.reshard.ParamSwapper`, so an adopted plan re-lays-out the
training state on device (clamped onto however many local devices exist)
and the reshard lands in the trace and metrics.  `--shift-at K` switches
the data mixture single-image → video at step K to force a mid-run drift.

`--hosts N` runs the loop *elastically* on an emulated fleet: the local
devices (force more with ``XLA_FLAGS=--xla_force_host_platform_device_count``)
split into N hosts owned by a `repro.launch.fleet.FleetManager`, each
global batch is sharded per host with exactly-once accounting, and
`--fail-host-at K` / `--revive-host-at K` drive a `FaultInjector` that
kills / revives the last host at those steps — the controller recovers
checkpoint-free (re-plan for the survivors + live param migration).

    PYTHONPATH=src python examples/train_mllm.py [--steps 200] [--random]
        [--trace runtime_trace.json] [--replan] [--shift-at 8]
        [--hosts 4 --fail-host-at 6 --revive-host-at 12]
"""
import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.common import compile_cache
from repro.common.types import MLLMConfig, ModalityStub, ModelConfig
from repro.core.engine import DFLOPEngine
from repro.core.optimizer.space import ClusterSpec, ModuleParallelism, ParallelismPlan
from repro.data.synthetic import MixedDataset
from repro.data.host_shard import HostShardedSource
from repro.launch.fleet import FaultInjector, FleetManager
from repro.launch.reshard import ParamSwapper, clamped_plan_mesh
from repro.runtime import DriftDetector
from repro.models import mllm as mllm_lib
from repro.models.model import FwdCtx
from repro.train import checkpoint
from repro.train.optim import AdamWConfig, adamw_init, cosine_lr
from repro.train.step import make_train_step

ENC = ModelConfig(name="enc-100m", family="vlm-enc", n_layers=6, d_model=384,
                  n_heads=6, n_kv_heads=6, d_ff=1536, vocab_size=0,
                  causal=False, use_rope=False, input_embed_dim=64,
                  has_lm_head=False, dtype="float32")
LLM = ModelConfig(name="llm-100m", family="dense", n_layers=8, d_model=512,
                  n_heads=8, n_kv_heads=4, d_ff=2048, vocab_size=8192,
                  dtype="float32")
MCFG = MLLMConfig(name="mllm-100m", encoder=ENC, llm=LLM,
                  stub=ModalityStub("vision", 16, 64), connector_hidden=512,
                  tokens_per_item_out=4)

TPM = 4          # connector tokens per media item
GBS = 16
MAX_MEDIA = 8 * 16       # encoder tokens cap
MAX_TEXT = 384


def build_batches(ds, plan, items, groups, n_mb, vocab_size=LLM.vocab_size):
    """Tensorize scheduler groups -> (n_mb, rows, ...) MLLM batch."""
    dp = plan.llm.dp
    rows = []
    for i in range(n_mb):
        row_items = []
        for r in range(dp):
            row_items += [items[j] for j in groups[i * dp + r]]
        rows.append(row_items or [items[0]])
    # pad rows to a power of two so batch shapes (and therefore jit
    # compilations) stay stable across steps; XLA CPU recompiles cost
    # minutes at this model size
    per_row = max(len(r) for r in rows)
    per_row = 1 << (per_row - 1).bit_length()
    batches = []
    for row_items in rows:
        row_items = (row_items * per_row)[:per_row]
        batches.append(ds.materialize(row_items, embed_dim=64,
                                      vocab_size=vocab_size,
                                      max_media=MAX_MEDIA, max_text=MAX_TEXT))
    return {k: jnp.asarray(np.stack([b[k] for b in batches]))
            for k in batches[0]}


def tiny_configs():
    """Sub-1M-param variant for smoke tests: compiles in seconds on CPU
    while exercising the identical control-loop + reshard code paths."""
    enc = ModelConfig(name="enc-tiny", family="vlm-enc", n_layers=2,
                      d_model=96, n_heads=4, n_kv_heads=4, d_ff=384,
                      vocab_size=0, causal=False, use_rope=False,
                      input_embed_dim=64, has_lm_head=False, dtype="float32")
    llm = ModelConfig(name="llm-tiny", family="dense", n_layers=2,
                      d_model=128, n_heads=4, n_kv_heads=2, d_ff=512,
                      vocab_size=1024, dtype="float32")
    mcfg = MLLMConfig(name="mllm-tiny", encoder=enc, llm=llm,
                      stub=ModalityStub("vision", 16, 64),
                      connector_hidden=128, tokens_per_item_out=4)
    return enc, llm, mcfg


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=200)
    ap.add_argument("--random", action="store_true",
                    help="random (data-agnostic) microbatch assignment")
    ap.add_argument("--ckpt", default="")
    ap.add_argument("--trace", default="",
                    help="export a Chrome trace of the run to this path")
    ap.add_argument("--replan", action="store_true",
                    help="enable background re-planning on drift, with "
                         "physical param resharding on plan hot-swap")
    ap.add_argument("--shift-at", type=int, default=0,
                    help="switch the data mixture single-image -> video at "
                         "this step (0 = keep the mixed stream)")
    ap.add_argument("--objective", default="mean",
                    choices=["mean", "expected-random", "balanced-quantile"],
                    help="search objective used by background re-planning")
    ap.add_argument("--compose-window", type=int, default=0,
                    help="lookahead batch composition over a window of "
                         "this many global batches (0 = FIFO draws)")
    ap.add_argument("--max-staleness", type=int, default=0,
                    help="max batches an item may wait in the compose "
                         "window (0 = default, 2x the window)")
    ap.add_argument("--tiny", action="store_true",
                    help="sub-1M-param model (CI smoke: compiles in "
                         "seconds, same control-loop code paths)")
    ap.add_argument("--hosts", type=int, default=0,
                    help="split the local devices into this many emulated "
                         "hosts and run elastically (0 = single-host)")
    ap.add_argument("--fail-host-at", type=int, default=0,
                    help="kill the last emulated host at this step "
                         "(requires --hosts; 0 = no failure)")
    ap.add_argument("--revive-host-at", type=int, default=0,
                    help="revive the killed host at this step")
    args = ap.parse_args()
    if (args.fail_host_at or args.revive_host_at) and not args.hosts:
        ap.error("--fail-host-at/--revive-host-at need --hosts")
    if args.hosts and args.random:
        ap.error("--random bypasses the controller, so fleet recovery "
                 "(poll_fleet) would never run; drop one of the two flags")
    if args.hosts and args.compose_window:
        ap.error("--hosts draws through the per-host sharded source; "
                 "combine it with --compose-window is not supported yet")
    if args.random and args.replan:
        ap.error("--random bypasses the control loop (schedule_random "
                 "never reaches the controller), so --replan would only "
                 "adopt plans at exit; drop one of the two flags")
    print(f"[cache] {compile_cache.enable()}")

    enc_cfg, llm_cfg, mcfg = tiny_configs() if args.tiny else (ENC, LLM, MCFG)
    if args.shift_at:
        ds = MixedDataset("single_image", seed=0, tokens_per_media_item=TPM)
        post_ds = MixedDataset("video", seed=1, tokens_per_media_item=TPM)
    else:
        ds = MixedDataset("mixed", seed=0, tokens_per_media_item=TPM)
        post_ds = None
    eng = DFLOPEngine(llm_cfg=llm_cfg, enc_cfg=enc_cfg, e_seq_len=16,
                      cluster=ClusterSpec(n_chips=16, chips_per_node=16),
                      tokens_per_media_item=TPM,
                      objective=args.objective)
    eng.profile(ds)
    plan = ParallelismPlan(llm=ModuleParallelism(1, 1, 1),
                           encoder=ModuleParallelism(1, 1, 1), n_mb=4)

    params = mllm_lib.init(jax.random.PRNGKey(0), mcfg)
    opt = adamw_init(params)
    n_params = sum(int(np.prod(x.shape)) for x in jax.tree_util.tree_leaves(params))
    print(f"[model] {n_params/1e6:.1f}M params  "
          f"devices={jax.device_count()}")

    # The controller reaches the live (params, opt) state through this
    # holder: a plan hot-swap physically re-lays-out both (optimizer state
    # moves with the parameters) on the plan's mesh, clamped onto the
    # local devices.
    live = {"state": (params, opt)}
    fleet = injector = None
    if args.hosts:
        fleet = FleetManager(n_hosts=args.hosts)
        schedule = {}
        victim = fleet.n_hosts - 1
        if args.fail_host_at:
            schedule[args.fail_host_at] = [("fail", victim)]
        if args.revive_host_at:
            schedule[args.revive_host_at] = [("join", victim)]
        injector = FaultInjector(fleet, schedule)
        print(f"[fleet] {fleet.n_hosts} hosts x "
              f"{fleet.devices_per_host} devices  schedule={schedule}")
    swapper = ParamSwapper(
        lambda: live["state"], lambda s: live.update(state=s),
        # fleet runs migrate onto the surviving roster; single-host runs
        # keep the device-count clamp
        mesh_factory=fleet.plan_mesh if fleet else clamped_plan_mesh)
    # tighter drift window than the default so a --shift-at demo fires
    # within a few global batches at GBS 16
    drift = DriftDetector(window=128, check_every=32, cooldown=64)
    ctl = eng.runtime(GBS, plan=plan, adaptive=True, ilp_time_limit_s=0.05,
                      auto_replan=args.replan, drift=drift,
                      param_swapper=swapper,
                      compose_window=args.compose_window,
                      max_staleness=args.max_staleness or None,
                      fleet=fleet)
    sched = ctl.scheduler
    composer = ctl.composer

    lr_fn = cosine_lr(1e-3, warmup=20, total=args.steps)
    step = jax.jit(make_train_step(mcfg, AdamWConfig(lr=1e-3),
                                   ctx=FwdCtx(mode="train")))

    hsrc = None
    if fleet is not None:
        current = {"ds": ds}
        hsrc = HostShardedSource(lambda: current["ds"].sample(GBS), GBS,
                                 fleet=fleet, keep_committed=False)

    losses, pred_cmax = [], []
    t0 = time.time()
    for k in range(args.steps):
        active_ds = post_ds if (post_ds and k >= args.shift_at) else ds
        if injector is not None:
            injector.on_step(k)      # roster mutates before this step draws
        if hsrc is not None:
            current["ds"] = active_ds
            shards = hsrc.draw()     # per-host split over the alive roster
            items = hsrc.in_flight
        elif composer is not None:
            # refills the window to capacity (first call warms the full
            # W-batch lookahead), then emits one composed batch
            items = ctl.compose(draw=lambda: active_ds.sample(GBS))
        else:
            items = active_ds.sample(GBS)
        out = (sched.schedule_random(items, seed=k) if args.random
               else ctl.schedule(items))       # may physically swap `live`
        pred_cmax.append(out.cmax)
        batch = build_batches(active_ds, out.plan, items, out.groups,
                              out.plan.n_mb, vocab_size=llm_cfg.vocab_size)
        params, opt = live["state"]
        ts = time.time()
        params, opt, m = step(params, opt, batch, lr_fn(k))
        m["loss"].block_until_ready()
        ctl.observe_step(out, time.time() - ts)
        # NaN (no MoE layers / unmeasured dispatch) is skipped, not recorded
        ctl.metrics.record_moe(float(m["moe_drop_rate"]),
                               float(m["moe_imbalance"]))
        live["state"] = (params, opt)
        if hsrc is not None:
            hsrc.commit()            # step survived: batch delivered once
        losses.append(float(m["loss"]))
        if k % 25 == 0:
            print(f"step {k:4d}  loss={losses[-1]:.3f}  "
                  f"pred C_max={out.cmax:.4f}s  solver={out.solver}")
    dt = time.time() - t0
    mode = "random" if args.random else "dflop"
    snap = ctl.metrics.snapshot()

    def fmt(key, scale=1.0, spec=".4f"):
        # snapshot stats are None when their window is empty ("no data")
        v = snap[key]
        return "n/a" if v is None else f"{v * scale:{spec}}"

    print(f"[{mode}] {args.steps} steps in {dt:.1f}s; "
          f"loss {losses[0]:.3f} -> {np.mean(losses[-10:]):.3f}; "
          f"mean predicted C_max {np.mean(pred_cmax):.4f}s")
    print(f"[runtime] imbalance={fmt('imbalance_mean')}  "
          f"sched_overhead={fmt('sched_elapsed_mean_s', 1e3, '.2f')}ms  "
          f"drift_events={snap['n_drift_events']}  "
          f"replans={snap['n_replans']}  "
          f"physical_swaps={snap['n_physical_swaps']}  "
          f"reshard_mean_s={fmt('reshard_mean_s')}  "
          f"moe_drop={fmt('moe_drop_rate_mean')}  "
          f"moe_imbalance={fmt('moe_imbalance_max')}")
    if fleet is not None:
        fl = snap["fleet"]
        print(f"[fleet] hosts={fleet.n_alive}/{fleet.n_hosts}  "
              f"failures={fl['n_host_failures']}  "
              f"joins={fl['n_host_joins']}  "
              f"recoveries={fl['n_recoveries']}  "
              f"degraded={fl['n_degraded']}  "
              f"committed={hsrc.n_committed}  aborted={hsrc.n_aborted}")
    if composer is not None:
        print(f"[compose] batches={snap['n_composed']}  "
              f"pred_gain_mean={fmt('compose_pred_gain_mean', 1.0, '.3f')}  "
              f"forced_items={snap['n_forced_items']}  "
              f"overhead={fmt('compose_elapsed_mean_s', 1e3, '.2f')}ms")
    if args.trace:
        print(f"chrome trace written to {ctl.export_trace(args.trace)}")
    ctl.close()
    params, opt = live["state"]
    if args.ckpt:
        checkpoint.save(args.ckpt, params, {"steps": args.steps,
                                            "loss": losses[-1]})
        print(f"checkpoint written to {args.ckpt}")


if __name__ == "__main__":
    main()
