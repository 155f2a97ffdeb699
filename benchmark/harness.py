"""What every traffic kind shares: the files of a cell, the device check, the
compile watch, the traced window, and the comparison that decides ``correct``.
"""

import dataclasses
import importlib
import json
import os
import shutil
import sys
import time
from typing import Any, Dict, List, Optional

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)

# ``correct`` (a): the engine or trainer against benchmark/reference.py, per
# token logprob. The system computes in bf16 (8 bits of mantissa) with f32
# accumulation and f32 logits; the reference in f32 throughout, from the same
# bf16-rounded (served) or f32 (trained) weights. The error grows with depth
# and width, so the two limits (mean and worst absolute difference) belong
# to the configuration: its file states them under ``logprob_limits`` as
# twice what that configuration read on the chip, with the reading. A forward
# pass in a lower precision than the configuration states fails them: an
# 8-bit float has 3 bits of mantissa, 32 times coarser than bf16, which puts
# the mean near 0.5. ``rehearse`` runs f32 against f32 on the CPU and is held
# to 1e-3.
REHEARSE_TOL = 1e-3


# ----------------------------------------------------------------- files --
def load_json(path):
    with open(path) as f:
        return json.load(f)


def load_bench():
    return load_json(os.path.join(ROOT, "BENCHMARK.json"))


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config_name: str
    config: Dict[str, Any]
    traffic_name: str
    traffic: Dict[str, Any]
    end_to_end: List[str]
    per_layer: List[str]


def load_cell(name, bench=None):
    """Everything a cell is, found by the names in ``BENCHMARK.json``."""
    bench = bench or load_bench()
    entry = next((w for w in bench["workloads"] if w["name"] == name), None)
    if entry is None:
        raise SystemExit(
            f"unknown workload {name!r}: BENCHMARK.json has "
            f"{[w['name'] for w in bench['workloads']]}"
        )
    cfg_entry = next(c for c in bench["configs"] if c["name"] == entry["config"])
    config = load_json(os.path.join(ROOT, cfg_entry["file"]))
    traffic = load_json(
        os.path.join(HERE, "traffic", entry["traffic"] + ".json")
    )

    def reported(section):
        return [
            m["name"] for m in bench[section]
            if name in m.get("workloads", [name])
        ]

    return Cell(
        name=name, chips=entry["chips"], config_name=entry["config"],
        config=config, traffic_name=entry["traffic"], traffic=traffic,
        end_to_end=reported("end_to_end"), per_layer=reported("per_layer"),
    )


def load_kind(kind):
    return importlib.import_module(f"benchmark.kinds.{kind}")


def read_metric(name, obs):
    """One metric through its own file ``benchmark/metrics/<name>.json`` and
    the reader that file names. None when there is nothing to read."""
    spec = load_json(os.path.join(HERE, "metrics", name + ".json"))
    reader = importlib.import_module(f"benchmark.readers.{spec['reader']}")
    value = reader.read(spec, obs)
    if value is None:
        return None
    return {"value": float(value), "unit": spec["unit"]}


# ---------------------------------------------------------------- device --
def model_config(cell, rehearse):
    """The program's ModelConfig from the cell's configuration file (or the
    program's tiny test configuration when rehearsing on the CPU)."""
    from areal_tpu.models.config import from_hf_config, tiny_config

    if rehearse:
        return tiny_config("qwen2")
    return from_hf_config(cell.config)


def reference_config(cell, rehearse):
    """The keys benchmark/reference.py and arith.py read."""
    if not rehearse:
        return cell.config
    from areal_tpu.models.config import tiny_config

    t = tiny_config("qwen2")
    return {
        "hidden_size": t.hidden_size, "intermediate_size": t.intermediate_size,
        "num_hidden_layers": t.num_layers, "head_dim": t.head_dim,
        "num_attention_heads": t.num_heads, "vocab_size": t.vocab_size,
        "num_key_value_heads": t.num_kv_heads, "rope_theta": t.rope_theta,
        "rms_norm_eps": t.rms_norm_eps,
        "tie_word_embeddings": t.tie_word_embeddings,
    }


def logprob_limits(cell, rehearse):
    """``correct`` (a)'s limits: the configuration's own (see the top of this
    file), or f32 against f32 when rehearsing."""
    if rehearse:
        return {"mean": REHEARSE_TOL, "worst": REHEARSE_TOL}
    return cell.config["logprob_limits"]


def device_info():
    import jax

    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def memory_peak_bytes():
    import jax

    peaks = [
        (d.memory_stats() or {}).get("peak_bytes_in_use", 0)
        for d in jax.devices()
    ]
    return int(max(peaks or [0]))


class CompileWatch:
    """Compile events of this process (``compiles``: a program served by the
    persistent cache still fires one, and counts) and how many of them the
    cache served (``cache_hits``). ``correct`` (c) is zero compile events
    inside the window, loaded or compiled; the loads are reported beside."""

    def __init__(self):
        from jax import monitoring

        self.compiles, self.seconds, self.cache_hits = 0, 0.0, 0
        monitoring.register_event_duration_secs_listener(self._on_duration)
        monitoring.register_event_listener(self._on_event)

    def _on_duration(self, event, duration, **kw):
        if event.endswith("backend_compile_duration"):
            self.compiles += 1
            self.seconds += duration

    def _on_event(self, event, **kw):
        if event.endswith("compilation_cache/cache_hits"):
            self.cache_hits += 1

    def mark(self):
        return self.compiles, self.cache_hits

    def since(self, mark):
        """(compile events, of them cache loads) since ``mark()``."""
        return self.compiles - mark[0], self.cache_hits - mark[1]

    def snapshot(self):
        return {"programs": self.compiles, "cache_hits": self.cache_hits,
                "compile_seconds": self.seconds}


# ----------------------------------------------------------------- spans --
def span(name):
    """A host span in the profiler's own trace, around a call into the
    program (nothing when no trace is running)."""
    import jax

    return jax.profiler.TraceAnnotation("bench:" + name)


class TracedWindow:
    """``with TracedWindow(on, dir):`` profiles what runs inside; afterwards
    ``.reduced`` holds benchmark.xplane's reduction (None when off). The
    whole block sits inside one ``bench:window`` span, and that span is the
    traced window: idle before the first device operation, after the last
    and between two calls into the program all count."""

    def __init__(self, on, trace_dir):
        self.on, self.dir, self.reduced = bool(on), trace_dir, None
        self.host_s = None
        self.profiler_s = 0.0  # starting and stopping the profiler itself

    def __enter__(self):
        if self.on:
            import jax

            shutil.rmtree(self.dir, ignore_errors=True)
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0  # the host's Python is not traced
            opts.host_tracer_level = 2  # TraceAnnotation spans are
            t = time.monotonic()
            jax.profiler.start_trace(self.dir, profiler_options=opts)
            self.profiler_s += time.monotonic() - t
            self._span = span("window")
            self._span.__enter__()
            self._t0 = time.monotonic()
        return self

    def __exit__(self, *exc):
        if self.on:
            import jax

            self.host_s = time.monotonic() - self._t0
            self._span.__exit__(*exc)
            t = time.monotonic()
            jax.profiler.stop_trace()
            self.profiler_s += time.monotonic() - t
        return False

    def reduce(self):
        if self.on and self.reduced is None:
            from benchmark import xplane

            events = xplane.load(xplane.find_xplane(self.dir))
            self.reduced = xplane.reduce(events, xplane.span_window(events))
        return self.reduced


# ----------------------------------------------------------- observation --
@dataclasses.dataclass
class Observation:
    """What a run saw, for the metric readers. ``counters`` are deltas over
    the window."""

    window_s: float
    setup_s: float
    counters: Dict[str, float] = dataclasses.field(default_factory=dict)
    values: Dict[str, float] = dataclasses.field(default_factory=dict)
    trace: Optional[Dict[str, Any]] = None
    peaks: Optional[Dict[str, float]] = None


@dataclasses.dataclass
class Outcome:
    """What a traffic kind hands back to run.py."""

    obs: Observation
    attempted: int
    failed: int
    checks: Dict[str, bool]
    notes: Dict[str, Any] = dataclasses.field(default_factory=dict)


def counter_delta(after, before):
    return {
        k: after[k] - before.get(k, 0.0)
        for k in after
        if isinstance(after[k], (int, float))
    }


# ------------------------------------------------------------ correctness --
def compare_logprobs(system, reference, limits):
    """Mean and worst absolute difference over all compared tokens, against
    ``limits`` = {"mean": .., "worst": ..} (``logprob_limits``)."""
    import numpy as np

    diffs = np.abs(
        np.concatenate([np.asarray(s, np.float64) for s in system])
        - np.concatenate([np.asarray(r, np.float64) for r in reference])
    )
    mean, worst = float(diffs.mean()), float(diffs.max())
    mean_tol, max_tol = float(limits["mean"]), float(limits["worst"])
    return {
        "compared": int(diffs.size), "mean_abs_diff": mean,
        "max_abs_diff": worst, "mean_tol": mean_tol, "max_tol": max_tol,
        "ok": bool(np.isfinite(diffs).all() and mean <= mean_tol
                   and worst <= max_tol),
    }


def log(msg):
    print(f"[bench {time.strftime('%H:%M:%S')}] {msg}", file=sys.stderr,
          flush=True)
