"""What every runner needs: the device gate, the compile counter, the result line."""

from __future__ import annotations

import json
import os
import sys
import time


def log(**obj):
    """One JSON object on a line of its own, before the result line."""
    print(json.dumps(obj), flush=True)


def device_info():
    import jax

    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind, "count": len(devs)}


def require_tpu(chips):
    """Refuse (no result line, exit 2) without a TPU or with fewer chips than asked."""
    info = device_info()
    if info["platform"] != "tpu" or info["count"] < chips:
        print(f"bench: needs {chips} TPU chip(s), jax found {info}", file=sys.stderr, flush=True)
        raise SystemExit(2)
    return info


def memory_peak_bytes():
    import jax

    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0) for d in jax.devices()]
    return int(max(peaks))


class CompileCounter:
    """Counts what jax built (cache hit or not) and the persistent-cache hits."""

    def __init__(self):
        import jax

        self.programs = 0
        self.seconds = 0.0
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._on_duration)
        jax.monitoring.register_event_listener(self._on_event)

    def _on_duration(self, event, duration, **_):
        if event.endswith("backend_compile_duration"):
            self.programs += 1
            self.seconds += duration

    def _on_event(self, event, **_):
        if event.endswith("compilation_cache/cache_hits"):
            self.cache_hits += 1

    def snapshot(self):
        return {"programs": self.programs, "seconds": round(self.seconds, 2), "cache_hits": self.cache_hits}


def enable_compile_cache(root):
    """The program's own switch: ``JAX_COMPILATION_CACHE_DIR`` if set, else its
    fixed ``<checkout>/.jax_cache``. ``root`` must be that checkout."""
    from paddlenlp_tpu.utils import env

    if not os.environ.get("JAX_COMPILATION_CACHE_DIR") and \
            os.path.realpath(os.path.dirname(env.DEFAULT_COMPILE_CACHE_DIR)) != os.path.realpath(root):
        raise RuntimeError("the program under test is not the one in this checkout")
    return env.enable_compile_cache()


NOT_MODEL_KEYS = ("bench", "architectures", "torch_dtype", "model_type", "use_cache")


def build_model(config, compute_dtype, param_dtype):
    """The program's model class on the configuration's published keys, without weights."""
    from . import loader

    b = config["bench"]
    cfg = loader.resolve(b["config_class"])(**{k: v for k, v in config.items() if k not in NOT_MODEL_KEYS})
    return cfg, lambda: loader.resolve(b["model_class"])(cfg, dtype=compute_dtype, param_dtype=param_dtype)


def adopt_params(model, params):
    """Hand the benchmark's seeded weights to the program, once their tree is the program's own."""
    import jax

    want = jax.tree.map(lambda s: (s.shape, s.dtype), model.param_shapes)
    if want != jax.tree.map(lambda a: (a.shape, a.dtype), params):
        raise RuntimeError("the reference's parameter tree is not the program's")
    model.params = params


class Tracer:
    """jax.profiler around a span of the window; the trace lands under
    ``<checkout>/bench_trace/<workload>`` (git-ignored, overwritten each run)."""

    def __init__(self, root, workload):
        self.dir = os.path.join(root, "bench_trace", workload)
        self.t_start = self.t_stop = None

    def start(self):
        import shutil

        import jax

        shutil.rmtree(self.dir, ignore_errors=True)
        os.makedirs(self.dir, exist_ok=True)
        jax.profiler.start_trace(self.dir)
        self.t_start = time.monotonic()

    def stop(self):
        import jax

        jax.profiler.stop_trace()
        self.t_stop = time.monotonic()

    def xplane_path(self):
        import glob

        found = sorted(glob.glob(os.path.join(self.dir, "plugins", "profile", "*", "*.xplane.pb")))
        if not found:
            raise FileNotFoundError(f"no .xplane.pb under {self.dir}")
        return found[-1]


def result_line(correct, attempted, failed, metrics, device, breakdown=None):
    line = {"correct": bool(correct), "attempted": int(attempted), "failed": int(failed),
            "metrics": metrics, "device": device}
    if breakdown:
        line["breakdown"] = breakdown
    print(json.dumps(line), flush=True)
