"""Pieces every driver shares: the device check, compile accounting, the
persistent compile cache, per-layer metric readers found by name, and
the result line."""
from __future__ import annotations

import collections
import importlib.util
import json
import math
import pathlib
import sys
import threading

ROOT = pathlib.Path(__file__).resolve().parent.parent
BENCH = ROOT / "bench"
CACHE_DIR = ROOT / ".bench_cache" / "jax"


class NoDevice(SystemExit):
    """Raised where JAX finds no TPU, or fewer than the cell asks for."""


def require_devices(chips: int) -> dict:
    """The device as JAX reports it; exits non-zero unless device 0 is
    a TPU and at least ``chips`` of them are visible."""
    import jax
    devs = jax.devices()
    info = {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}
    if info["platform"] != "tpu":
        print(f"[bench] no TPU: JAX runs on {info['platform']}",
              file=sys.stderr, flush=True)
        raise NoDevice(2)
    if info["count"] < chips:
        print(f"[bench] {info['count']} chips visible, the cell asks "
              f"for {chips}", file=sys.stderr, flush=True)
        raise NoDevice(2)
    return info


def enable_cache() -> str:
    """JAX's persistent compile cache at a fixed path inside the
    checkout, with every program kept (the GP programs compile in well
    under JAX's default one-second threshold)."""
    import jax
    CACHE_DIR.mkdir(parents=True, exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return str(CACHE_DIR)


class CompileCounter:
    """Compiles (every program that reached the backend, compiled or
    loaded from the persistent cache), their seconds, and persistent
    cache hits and misses, from JAX's own monitoring events."""

    def __init__(self):
        import jax
        self.n = collections.Counter()
        self.secs = 0.0
        self.names = []
        self._lock = threading.Lock()
        jax.monitoring.register_event_listener(self._event)
        jax.monitoring.register_event_duration_secs_listener(self._duration)

    def _event(self, event, **_):
        with self._lock:
            self.n[event] += 1

    def _duration(self, event, secs, fun_name=None, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            with self._lock:
                self.secs += secs
                self.n["compiles"] += 1
                self.names.append(str(fun_name))

    def mark(self):
        with self._lock:
            return self.secs, collections.Counter(self.n), len(self.names)

    def since(self, mark) -> dict:
        secs, n, k = mark
        with self._lock:
            d = self.n - n
            return {"compile_s": self.secs - secs,
                    "compiles": d["compiles"],
                    "programs": sorted(collections.Counter(
                        self.names[k:]).items()),
                    "cache_hits": d["/jax/compilation_cache/cache_hits"],
                    "cache_misses": d["/jax/compilation_cache/cache_misses"]}


def load_module(path: pathlib.Path):
    spec = importlib.util.spec_from_file_location(
        f"bench_{path.parent.name}_{path.stem}".replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def read_layer_metrics(metrics, run, directory=BENCH / "metrics") -> dict:
    """Each per-layer metric's reader, ``<directory>/<name>.py``'s
    ``read(run)``; a reader that finds nothing returns None and the
    metric is left out."""
    out = {}
    for m in metrics:
        value = load_module(directory / f"{m['name']}.py").read(run)
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


def applies(metric: dict, workload: str) -> bool:
    return "workloads" not in metric or workload in metric["workloads"]


def result_line(correct, attempted, failed, metrics, device, checks,
                breakdown=None) -> str:
    out = {"correct": bool(correct), "attempted": int(attempted),
           "failed": int(failed), "metrics": metrics, "device": device}
    if breakdown is not None:
        out["breakdown"] = breakdown
    out["checks"] = {name: {"value": value, "limit": limit}
                     for name, value, limit in checks}
    return json.dumps(out, allow_nan=False, default=_num)


def _num(x):
    v = float(x)
    return v if math.isfinite(v) else None
