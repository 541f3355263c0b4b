"""One-chip training cell: DFLOP's train path, driven through its entry points.

Set-up builds one object, the compiled step with its state, and drives it
through the first ``DRIVEN_STEPS`` steps with the same feed and call as the
window (every row differs).  The window then runs the same object for the
cell's seconds:

    traffic -> RuntimeController.schedule -> MixedDataset.materialize
      -> device_put -> compiled make_train_step -> observe_step

Each step ends in ``block_until_ready``.  After the window the program's
state is freed and the plain reference (``bench/reference``) follows the
driven steps on the same weights and batches.
"""
from __future__ import annotations

import gc
import time

import jax
import jax.numpy as jnp
import numpy as np

from bench import flops as flops_lib
from bench.harness import check, tracing
from bench.harness.traffic import Traffic
from bench.reference import mllm as ref

DRIVEN_STEPS = 3
_COMPILES = [0]


def _count_compiles(event: str, duration: float, **kw) -> None:
    if event.endswith("backend_compile_duration"):
        _COMPILES[0] += 1


jax.monitoring.register_event_duration_secs_listener(_count_compiles)


def _span(name: str, **kw):
    return jax.profiler.TraceAnnotation(f"bench.{name}", **kw)


def to_desc(m: dict):
    """The program's MLLMConfig from the configuration file's ``model``."""
    from repro.common.types import MLLMConfig, ModalityStub, ModelConfig

    def stack(c):
        c = dict(c)
        c["layer_pattern"] = tuple(c["layer_pattern"])
        c["ffn_pattern"] = tuple(c["ffn_pattern"])
        return ModelConfig(**c)

    return MLLMConfig(name=m["name"], encoder=stack(m["encoder"]),
                      llm=stack(m["llm"]), stub=ModalityStub(**m["stub"]),
                      connector_hidden=m["connector_hidden"],
                      tokens_per_item_out=m["tokens_per_item_out"])


class TrainRun:
    """The cell's program state and its feed."""

    def __init__(self, cfg: dict, traffic: dict, seed: int):
        self.spec, self.seed = traffic, int(seed)
        self.m = cfg["model"]
        self.opt_cfg = cfg["optimizer"]
        self.desc = to_desc(self.m)
        self.dev = jax.devices()[0]
        self.traffic = Traffic(traffic, seed, self.desc.tokens_per_item_out)
        self.n_mb = int(traffic["microbatches"])
        self.rows = int(traffic["rows_per_microbatch"])
        self.media_cap = int(traffic["media_cap"])
        self.text_cap = int(traffic["text_cap"])
        self.t_media = self.media_cap * self.desc.stub.n_tokens
        self.flops_per_step = flops_lib.step_flops(
            self.m, self.n_mb * self.rows, self.t_media, self.text_cap)

    # ------------------------------------------------------------------ #
    def setup(self) -> None:
        from repro.core.engine import DFLOPEngine
        from repro.core.optimizer.space import (ClusterSpec, ModuleParallelism,
                                                ParallelismPlan)
        from repro.data.items import DataItem
        from repro.data.synthetic import MixedDataset
        from repro.models import mllm as mllm_lib
        from repro.models.model import FwdCtx
        from repro.train.optim import AdamWConfig, adamw_init
        from repro.train.step import make_train_step

        self.DataItem = DataItem
        desc = self.desc
        pool = [DataItem(a, b, k, i)
                for i, (a, b, k) in enumerate(self.traffic.pool)]
        eng = DFLOPEngine(llm_cfg=desc.llm, enc_cfg=desc.encoder,
                          e_seq_len=desc.stub.n_tokens,
                          cluster=ClusterSpec(n_chips=1, chips_per_node=1),
                          tokens_per_media_item=desc.tokens_per_item_out)
        eng.profile(items=pool)
        plan = ParallelismPlan(llm=ModuleParallelism(1, 1, 1),
                               encoder=ModuleParallelism(1, 1, 1),
                               n_mb=self.n_mb)
        self.ctl = eng.runtime(self.traffic.items_per_step, plan=plan,
                               auto_replan=False)
        self.ds = MixedDataset(dict(self.spec["mixture"]), seed=0,
                               tokens_per_media_item=desc.stub.n_tokens)

        self.key = ref.seed_key(self.seed)
        m = self.m
        self.init = jax.jit(lambda k: ref.init_params(k, m))
        want = jax.tree.structure(jax.eval_shape(
            lambda k: mllm_lib.init(k, desc), jax.random.PRNGKey(0)))
        params = jax.device_put(self.init(self.key), self.dev)
        if jax.tree.structure(params) != want:
            raise RuntimeError("benchmark weights do not match the program's "
                               "parameter tree")
        opt = jax.jit(adamw_init)(params)
        self.lr = jax.device_put(jnp.float32(self.opt_cfg["lr"]), self.dev)
        step = jax.jit(make_train_step(desc, AdamWConfig(**self.opt_cfg),
                                       ctx=FwdCtx(mode="train")),
                       donate_argnums=(0, 1))
        self.step = step.lower(params, opt, self.batch_shapes(),
                               self.lr).compile()
        self.memory_analysis = self.step.memory_analysis()
        self.params, self.opt = params, opt
        b1 = self.opt_cfg["b1"]
        self._grad_norms = jax.jit(
            lambda mom: ref.leaf_norms(mom) / (1.0 - b1))
        self._update_norms = jax.jit(lambda p, k: ref.leaf_norms(
            jax.tree.map(jnp.subtract, p, ref.init_params(k, m))))

    def batch_shapes(self) -> dict:
        lead = (self.n_mb, self.rows)
        E = self.desc.stub.embed_dim
        i32 = jnp.int32
        sd = jax.ShapeDtypeStruct
        return {"media_embeds": sd(lead + (self.t_media, E), jnp.float32),
                "media_mask": sd(lead + (self.t_media,), i32),
                "text_tokens": sd(lead + (self.text_cap,), i32),
                "text_mask": sd(lead + (self.text_cap,), i32),
                "labels": sd(lead + (self.text_cap,), i32)}

    # ------------------------------------------------------------------ #
    def layout(self, groups, items) -> list[list[list]]:
        """Rows of each microbatch: the scheduler's group ``i`` fills
        microbatch ``i``; its items go round-robin into the rows, and the
        items that share a row are packed into it."""
        if len(groups) != self.n_mb:
            raise RuntimeError(f"scheduler gave {len(groups)} groups for "
                               f"{self.n_mb} microbatches")
        return [[[items[j] for j in g[r::self.rows]] for r in range(self.rows)]
                for g in groups]

    def feed(self, k: int):
        """Schedule, tensorize and place global step ``k``.  Returns the
        device batch, the host batch, the schedule and the step's counts."""
        with _span("schedule", step=k):
            items = self.traffic.step_items(k)
            out = self.ctl.schedule([
                self.DataItem(it.n_media, it.text_len, it.kind, it.item_id)
                for it in items])
            layout = self.layout(out.groups, items)
        with _span("materialize", step=k):
            host, counts = self.materialize(k, layout)
        with _span("device_put", step=k):
            batch = jax.device_put(host, self.dev)
        return batch, host, out, counts

    def materialize(self, k: int, layout):
        """The program's ``materialize`` over the rows of each microbatch.
        A row left empty (a group with fewer items than rows) is all
        padding; ``materialize`` cannot make one (it fails on no text)."""
        n_tok, tpo = self.desc.stub.n_tokens, self.desc.tokens_per_item_out
        pad = {key: np.zeros(s.shape[2:], s.dtype)
               for key, s in self.batch_shapes().items()}
        pad["labels"] = pad["labels"] - 1
        mbs, tokens, bad = [], 0, 0
        for i, rows in enumerate(layout):
            packed = [self.DataItem(sum(it.n_media for it in r),
                                    sum(it.text_len for it in r))
                      for r in rows]
            full = [r for r, it in enumerate(packed) if it.text_len > 0]
            seed = int(np.random.SeedSequence([self.seed, k, i])
                       .generate_state(1, np.uint64)[0])
            made = self.ds.materialize(
                [packed[r] for r in full], embed_dim=self.desc.stub.embed_dim,
                vocab_size=self.desc.llm.vocab_size, max_media=self.t_media,
                max_text=self.text_cap, seed=seed)
            mb = {key: np.stack([pad[key]] * self.rows) for key in pad}
            for j, r in enumerate(full):
                for key in mb:
                    mb[key][r] = made[key][j]
            for r, it in enumerate(packed):
                media = min(it.n_media_items, self.media_cap)
                text = min(it.text_len, self.text_cap)
                tokens += media * tpo + text
                bad += int(mb["media_mask"][r].sum() != min(
                    it.n_media_items * n_tok, self.t_media))
                bad += int(mb["text_mask"][r].sum() != text)
                bad += int(not np.array_equal(mb["labels"][r],
                                              ref.next_token_labels(
                                                  mb["text_tokens"][r],
                                                  mb["text_mask"][r])))
            mbs.append(mb)
        host = {key: np.stack([mb[key] for mb in mbs]) for key in mbs[0]}
        counts = {"tokens": tokens, "bad_rows": bad,
                  "media_slots": host["media_mask"].size,
                  "media_real": int(host["media_mask"].sum()),
                  "text_slots": host["text_mask"].size,
                  "text_real": int(host["text_mask"].sum())}
        return host, counts

    def run_step(self, batch):
        with _span("step"):
            t0 = time.perf_counter()
            self.params, self.opt, met = self.step(self.params, self.opt,
                                                   batch, self.lr)
            jax.block_until_ready((self.params, self.opt, met))
            return met, time.perf_counter() - t0

    # ------------------------------------------------------------------ #
    def drive(self, log) -> dict:
        """The driven steps: the program's readings for the check."""
        losses, batches = [], []
        grad_norms, failed = None, 0
        for k in range(DRIVEN_STEPS):
            batch, host, out, counts = self.feed(k)
            met, step_s = self.run_step(batch)
            self.ctl.observe_step(out, step_s)
            losses.append(float(met["loss"]))
            batches.append(host)
            failed += int(counts["bad_rows"] > 0
                          or not np.isfinite(losses[-1]))
            if k == 0:
                grad_norms = np.asarray(self._grad_norms(self.opt["m"]))
            log(f"[setup] driven step {k}: loss {losses[-1]:.6f}, "
                f"{step_s:.4f} s")
        update_norms = np.asarray(self._update_norms(self.params, self.key))
        self.driven_batches = batches
        return {"losses": losses, "grad_norms": grad_norms,
                "update_norms": update_norms, "failed": failed}

    def window(self, seconds: float, first_step: int) -> dict:
        """Steps until ``seconds`` have passed; whole steps only."""
        steps, failed = [], 0
        k = first_step
        compiles = _COMPILES[0]
        t_w0 = time.perf_counter()
        with _span("window"):
            while True:
                batch, _, out, counts = self.feed(k)
                met, step_s = self.run_step(batch)
                with _span("observe", step=k):
                    self.ctl.observe_step(out, step_s)
                loss = float(met["loss"])
                t_end = time.perf_counter()
                bad = counts["bad_rows"] > 0 or not np.isfinite(loss)
                failed += int(bad)
                steps.append({**counts, "step_s": step_s,
                              "pred_s": float(out.step_makespan),
                              "flops": self.flops_per_step,
                              "t_end": t_end - t_w0})
                k += 1
                if t_end - t_w0 >= seconds:
                    break
        return {"steps": steps, "failed": failed,
                "window_s": steps[-1]["t_end"],
                "compiles": _COMPILES[0] - compiles}

    def free(self) -> None:
        """Drop the program's state and stop its controller's threads."""
        self.ctl.close()
        del self.params, self.opt, self.step
        gc.collect()

    def ref_batches(self) -> list:
        """The driven steps' batches as the reference takes them: per step,
        per microbatch, a list of single-row dicts, without the program's
        labels."""
        return [[[{key: v[i, j:j + 1] for key, v in host.items()
                   if key != "labels"}
                  for j in range(self.rows)] for i in range(self.n_mb)]
                for host in self.driven_batches]

    def reference(self, precision: str = "highest") -> dict:
        """The reference's readings over the driven steps' batches."""
        return ref.Reference(self.m, self.opt_cfg, precision).run(
            self.key, self.ref_batches(), self.init)

    def leaf_names(self) -> list[str]:
        return ref.leaf_names(jax.eval_shape(self.init, self.key))


def run_cell(cell: dict, cfg: dict, traffic: dict, limits: dict, *,
             seed: int, seconds: float, trace: bool, t_start: float,
             log) -> dict:
    """One run of the cell: set-up, driven steps, window, check."""
    run = TrainRun(cfg, traffic, seed)
    run.setup()
    ma = run.memory_analysis
    log(f"[setup] compiled step: arguments {ma.argument_size_in_bytes} B, "
        f"temporaries {ma.temp_size_in_bytes} B")
    prog = run.drive(log)
    if trace:
        seconds = min(seconds, tracing.TRACE_SECONDS)
    with tracing.Tracer(trace) as tr:
        setup_s = time.perf_counter() - t_start
        win = run.window(seconds, DRIVEN_STEPS)
    log(f"[window] {len(win['steps'])} steps in {win['window_s']:.3f} s, "
        f"{win['compiles']} compilations inside it")
    dev = run.dev
    peak = (dev.memory_stats() or {}).get("peak_bytes_in_use")
    run.free()
    refr = run.reference()
    numbers = check.readings(prog, refr)
    names = run.leaf_names()
    log(f"[check] losses {prog['losses']} reference {refr['losses']}")
    log(f"[check] worst gradient leaf {names[numbers['grad_leaf']]}, "
        f"worst update leaf {names[numbers['update_leaf']]}, "
        f"{numbers['quiet_leaves']} quiet leaves left out")
    correct, checks = check.decide(numbers, limits)
    failed = prog["failed"] + win["failed"]
    return {"correct": correct and failed == 0,
            "attempted": DRIVEN_STEPS + len(win["steps"]),
            "failed": failed, "checks": checks,
            "setup_s": setup_s, "window": win,
            "trace": tr.result if trace else None,
            "memory_peak_bytes": peak, "chips": 1}
