"""Runner for ``kind: train``: Trainer + TrainingArguments as ``llm/run_pretrain.py``
builds them, one ``train()`` call. Its first steps are the ones the reference
follows; the window is a later stretch of the same call, timed by a callback."""

from __future__ import annotations

import gc
import os
import time

import numpy as np

from . import loader
from .common import (CompileCounter, Tracer, adopt_params, build_model, enable_compile_cache, log,
                     memory_peak_bytes)


class SeededRows:
    """Seeded, learnable rows: each walks a slice of the vocabulary with its own
    pair of start and stride (a seeded permutation of the pairs, so no two rows
    are alike) and the loss can fall. Remembers the order in which rows were
    asked for, so the reference can be given the same batches without reading
    anything the program made."""

    def __init__(self, seed, job, vocab):
        self.span = min(job["vocab_span"], vocab)
        if job["rows"] > 7 * self.span:
            raise ValueError("more rows than pairs of start and stride")
        pairs = np.random.default_rng(seed).permutation(7 * self.span)[: job["rows"]]
        self.starts, self.strides = pairs % self.span, 1 + pairs // self.span
        self.seq_len = job["seq_len"]
        self.asked = []

    def __len__(self):
        return len(self.starts)

    def row(self, i):
        return ((self.starts[i] + self.strides[i] * np.arange(self.seq_len)) % self.span).astype(np.int32)

    def __getitem__(self, i):
        self.asked.append(int(i))
        ids = self.row(i)
        return {"input_ids": ids, "labels": ids.copy()}


def make_callback(base, job, seconds, trace, ref, config, seed, t_process):
    import jax
    import jax.numpy as jnp

    class BenchCallback(base):
        """Times the steps (each ``on_log`` follows the Trainer's block on that
        step's loss), reads the first steps' numbers, and stops the run."""

        def __init__(self):
            self.trainer = None
            self.losses, self.step_ends = [], []
            self.first_grad = self.param_delta = None
            self.t_window = self.setup_s = None
            self.window_ends = []
            self.tracer = trace
            self._ann = None
            self.trace_steps = None

        def on_step_begin(self, args, state, control, **kw):
            self._ann = jax.profiler.StepTraceAnnotation("bench_step", step_num=state.global_step + 1)
            self._ann.__enter__()

        def on_step_end(self, args, state, control, **kw):
            step, ts = state.global_step, self.trainer.train_state
            if step == 1:
                mu = next(s.mu for s in jax.tree.leaves(ts.opt_state, is_leaf=lambda x: hasattr(x, "mu"))
                          if hasattr(s, "mu"))
                norms = jax.jit(lambda t: {k: jnp.linalg.norm(v.astype(jnp.float32).ravel())
                                           for k, v in ref.program_leaves(t).items()})(mu)
                self.first_grad = {k: v / (1.0 - args.adam_beta1) for k, v in norms.items()}
            if step == job["check_steps"]:
                dtype = jax.tree.leaves(ts.params)[0].dtype
                self.param_delta = jax.jit(lambda p, s: {
                    k: jnp.linalg.norm((v - w).astype(jnp.float32).ravel()) for (k, v), w in zip(
                        ref.program_leaves(p).items(),
                        ref.program_leaves(ref.program_params(config, s, dtype)).values())})(
                    ts.params, ref.seed_array(seed))

        def on_log(self, args, state, control, logs=None, **kw):
            if self._ann is not None:
                self._ann.__exit__(None, None, None)
                self._ann = None
            if not logs or "loss" not in logs:
                return
            now, step = time.monotonic(), state.global_step
            self.losses.append(logs["loss"])
            self.step_ends.append(now)
            if step == job["warm_steps"]:
                self.t_window, self.setup_s = now, now - t_process
                self.window_ends = [now]
            elif self.t_window is not None:
                if now <= self.t_window + seconds:
                    self.window_ends.append(now)
                else:
                    control.should_training_stop = True
                if self.tracer is not None:
                    n = len(self.window_ends) - 1
                    if n == 2 and self.tracer.t_start is None:
                        self.tracer.start()
                    elif n == 2 + job["trace_steps"] and self.tracer.t_stop is None:
                        self.tracer.stop()

    return BenchCallback()


def run(cell, args, t_process, root):
    import jax
    import jax.numpy as jnp

    config, job = cell["config"], cell["traffic"]
    b = config["bench"]
    cache_dir = enable_compile_cache(root)
    compiles = CompileCounter()
    ref = loader.module_from("reference", b["reference"])

    from paddlenlp_tpu.trainer import Trainer, TrainerCallback, TrainingArguments
    from paddlenlp_tpu.transformers import LlmMetaConfig

    out_dir = os.path.join(root, "bench_trace", cell["workload"]["name"] + ".run")
    os.makedirs(out_dir, exist_ok=True)
    seed = args.seed % (2**31 - 1)
    targs = TrainingArguments(output_dir=out_dir, max_steps=10**6, seed=seed, save_strategy="no",
                              disable_tqdm=True, **b["training"])
    wdtype = jnp.dtype(b["precision"]["weights"])
    cfg, make = build_model(config, jnp.dtype(b["precision"]["compute"]), wdtype)
    LlmMetaConfig.set_llm_config(cfg, targs)
    cfg.use_cache = False
    model = make()
    adopt_params(model, jax.jit(lambda s: ref.program_params(config, s, wdtype))(ref.seed_array(args.seed)))

    data = SeededRows(args.seed, job, config["vocab_size"])
    tracer = Tracer(root, cell["workload"]["name"]) if args.trace else None
    cb = make_callback(TrainerCallback, job, args.seconds, tracer, ref, config, args.seed, t_process)
    trainer = Trainer(model=model, args=targs, train_dataset=data, callbacks=[cb])
    cb.trainer = trainer
    rows = targs.global_train_batch_size
    trainer.train()
    jax.block_until_ready(trainer.train_state.params)
    if tracer is not None and tracer.t_stop is None:
        raise RuntimeError("the window was too short to trace: raise --seconds")
    peak = memory_peak_bytes()
    ends = cb.window_ends
    n_steps = len(ends) - 1
    chips = cell["workload"]["chips"]
    tokens_step = rows * job["seq_len"]
    rate = n_steps * tokens_step / (ends[-1] - ends[0]) / chips if n_steps > 0 else float("nan")
    first_grad = {k: float(v) for k, v in cb.first_grad.items()}
    param_delta = {k: float(v) for k, v in cb.param_delta.items()}
    losses = [float(x) for x in cb.losses]
    log(phase="window", cache_dir=cache_dir, seconds=args.seconds, setup_s=round(cb.setup_s, 2), steps=n_steps,
        rows=rows, seq_len=job["seq_len"], train_tokens_per_s=rate, mesh=dict(trainer.mesh.shape),
        step_s={"median": float(np.median(np.diff(ends))) if n_steps else None,
                "min": float(np.min(np.diff(ends))) if n_steps else None,
                "max": float(np.max(np.diff(ends))) if n_steps else None},
        first_losses=losses[:5], last_loss=losses[-1], compile=compiles.snapshot())

    k = job["check_steps"]
    batches = [np.stack([data.row(i) for i in data.asked[s * rows:(s + 1) * rows]]) for s in range(k)]
    model.params = None
    trainer.train_state = None
    del trainer, model
    gc.collect()
    t_check = time.monotonic()
    optim = {key: b["training"][key] for key in ("adam_beta1", "adam_beta2", "adam_epsilon", "learning_rate",
                                                "weight_decay", "max_grad_norm")}
    want = ref.train_trajectory(config, args.seed, batches, optim)
    numbers = {
        "loss_gap": max(abs(a - w) / w for a, w in zip(losses[:k], want["losses"])),
        "first_grad_gap": ref.worst_leaf_gap(first_grad, want["first_grad_norm"]),
        "param_delta_gap": ref.worst_leaf_gap(param_delta, want["param_delta_norm"]),
    }
    control = None
    if args.control:
        low = ref.train_trajectory(config, args.seed, batches, optim, precision=args.control)
        control = {
            "loss_gap": max(abs(a - w) / w for a, w in zip(low["losses"], want["losses"])),
            "first_grad_gap": ref.worst_leaf_gap(low["first_grad_norm"], want["first_grad_norm"]),
            "param_delta_gap": ref.worst_leaf_gap(low["param_delta_norm"], want["param_delta_norm"]),
        }
    limits = b["limits"]
    reasons = [f"{name} {numbers[name]} over limit {limits[name]}" for name in numbers
               if limits[name] is None or not numbers[name] <= limits[name]]
    if not all(np.isfinite(losses)):
        reasons.append("a loss is not finite")
    if len(set(tuple(r) for bt in batches for r in bt.tolist())) != k * rows:
        reasons.append("the checked rows do not all differ")
    log(phase="check", compared=numbers, limits=limits, control=control, reference_losses=want["losses"],
        rows_checked=data.asked[:k * rows],
        program_losses=losses[:k], seconds=round(time.monotonic() - t_check, 2), reasons=reasons)

    run_info = {"kind": "train", "config": config, "step_ends": ends, "seq_len": job["seq_len"],
                "rows_per_chip": targs.per_device_train_batch_size,
                "train_tokens_per_s": rate, "tracer": tracer, "peaks": loader.peaks(jax.devices()[0].device_kind)}
    return {"correct": not reasons, "attempted": n_steps, "failed": 0,
            "end_to_end": {"train_tokens_per_s": rate, "setup_s": cb.setup_s},
            "memory_peak_bytes": peak, "run": run_info}
