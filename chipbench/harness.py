"""What every cell shares: finding a cell's files by its name, the look for
the chip, the compile cache, JAX's compile events, the profiler window, the
per-layer readers and the result line."""
from __future__ import annotations

import glob
import importlib
import json
import os
import shutil
import sys
import time
from typing import Callable, Dict, List, Optional

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
HERE = os.path.join(ROOT, "chipbench")
OUT = os.path.join(ROOT, "chipbench_out")
# where a cell's files are looked for by name; the tests add the directory
# that holds the four-chip cell's files until a PR brings that cell
DATA_DIRS = [HERE]


def process_age_s() -> float:
    """Seconds since this process was started, by the kernel's own record:
    ``setup_s`` counts the interpreter's start and every import."""
    with open("/proc/self/stat") as f:
        start_ticks = int(f.read().rsplit(")", 1)[1].split()[19])
    with open("/proc/uptime") as f:
        up = float(f.read().split()[0])
    return up - start_ticks / os.sysconf("SC_CLK_TCK")


def load_json(path: str) -> Dict:
    with open(path) as f:
        return json.load(f)


def find(*parts: str) -> Optional[str]:
    """The file ``<kind>/<name>`` under the first data directory that has it."""
    for d in DATA_DIRS:
        path = os.path.join(d, *parts)
        if os.path.exists(path):
            return path
    return None


class Cell:
    """One entry of ``workloads`` in BENCHMARK.json with its files:
    ``<config>.json`` by the configuration's ``file`` and the traffic mix or
    training job ``chipbench/traffic/<traffic>.json`` or
    ``chipbench/jobs/<traffic>.json``."""

    def __init__(self, name: str, rehearsal: bool = False):
        bench = load_json(os.path.join(ROOT, "BENCHMARK.json"))
        cells = {w["name"]: w for w in bench["workloads"]}
        if name not in cells:
            raise SystemExit(f"chipbench: no workload {name!r} in "
                             f"BENCHMARK.json; have {sorted(cells)}")
        self.name = name
        self.entry = cells[name]
        self.chips = int(self.entry["chips"])
        cfg_entry = {c["name"]: c for c in bench["configs"]}[self.entry["config"]]
        self.config = load_json(os.path.join(ROOT, cfg_entry["file"]))
        path = (find("traffic", self.entry["traffic"] + ".json")
                or find("jobs", self.entry["traffic"] + ".json"))
        if path is None:
            raise SystemExit(f"chipbench: no traffic or job file for "
                             f"{self.entry['traffic']!r}")
        self.mix = load_json(path)
        self.rehearsal = rehearsal
        if rehearsal:
            # tiny widths and lengths for the sandbox's CPU: every key of the
            # rehearsal file overrides the same key of the real file
            over = load_json(find("rehearsal", self.entry["traffic"] + ".json"))
            self.config = {**self.config, **over["config"]}
            mix = {**self.mix, **over["mix"]}
            if "engine" in over["mix"]:
                mix["engine"] = {**self.mix["engine"], **over["mix"]["engine"]}
            self.mix = mix
        self.end_to_end = [m for m in bench["end_to_end"]
                           if name in m.get("workloads", [name])]
        self.per_layer = [m for m in bench["per_layer"]
                          if name in m.get("workloads", [name])]


def program_config(c: Dict, max_len: int, remat: bool = True):
    """The configuration file's keys as the program's ``LlamaConfig``: bf16,
    every width as published."""
    import jax.numpy as jnp
    from paddle_tpu.models import llama
    return llama.LlamaConfig(
        vocab_size=c["vocab_size"], hidden_size=c["hidden_size"],
        intermediate_size=c["intermediate_size"],
        num_layers=c["num_hidden_layers"], num_heads=c["num_attention_heads"],
        num_kv_heads=c["num_key_value_heads"],
        head_dim=int(c.get("head_dim")
                     or c["hidden_size"] // c["num_attention_heads"]),
        max_seq_len=max_len, rope_theta=c["rope_theta"],
        rms_eps=c["rms_norm_eps"], dtype=jnp.bfloat16,
        tie_embeddings=c["tie_word_embeddings"], remat=remat)


def start_jax(cell: Cell):
    """Import JAX on the right platform, place the compile cache at its fixed
    path inside the checkout, and refuse to go on without the chips the cell
    asks for. Returns (jax, device dict, peaks row)."""
    if cell.rehearsal:
        os.environ["JAX_PLATFORMS"] = "cpu"
        flags = os.environ.get("XLA_FLAGS", "")
        if cell.chips > 1 and "host_platform_device_count" not in flags:
            os.environ["XLA_FLAGS"] = (
                flags + f" --xla_force_host_platform_device_count={cell.chips}")
    import jax
    from . import arith
    if cell.rehearsal:
        # a cache of its own: CPU programs built under the rehearsal's flags
        # must not be found by the program's CPU tests, which share
        # artifacts/xla_cache and compare bit for bit
        path = os.path.join(OUT, "xla_cache_cpu")
        os.makedirs(path, exist_ok=True)
        jax.config.update("jax_compilation_cache_dir", path)
    elif not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        path = os.path.join(ROOT, "artifacts", "xla_cache")
        os.makedirs(path, exist_ok=True)
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    devs = jax.devices()
    dev = devs[0]
    if cell.rehearsal:
        from paddle_tpu.ops.pallas import flash_attention as fa
        fa.set_interpret(True)
        peaks = None
    else:
        if dev.platform != "tpu":
            raise SystemExit(f"chipbench: no accelerator: JAX reports "
                             f"platform {dev.platform!r}; nothing was run")
        if len(devs) < cell.chips:
            raise SystemExit(f"chipbench: {cell.name} needs {cell.chips} "
                             f"chips, JAX reports {len(devs)}")
        peaks = arith.load_peaks(dev.device_kind)
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": cell.chips}
    return jax, device, peaks


class CompileMeter:
    """Compilations and persistent-cache loads, from JAX's own monitoring
    events (as ``chip_smoke.py`` counts them)."""

    def __init__(self, jax):
        self.requests = self.hits = 0
        self.compile_s = 0.0
        jax.monitoring.register_event_listener(self._event)
        jax.monitoring.register_event_duration_secs_listener(self._dur)

    def _event(self, event, **kw):
        if event == "/jax/compilation_cache/compile_requests_use_cache":
            self.requests += 1
        elif event == "/jax/compilation_cache/cache_hits":
            self.hits += 1

    def _dur(self, event, duration, **kw):
        if event.startswith("/jax/core/compile/"):
            self.compile_s += duration


def memory_peak_bytes(jax, chips: int) -> int:
    """The peak on the fullest chip, as the runtime reports it."""
    peaks = []
    for d in jax.devices()[:chips]:
        st = d.memory_stats() or {}
        peaks.append(int(st.get("peak_bytes_in_use", 0)))
    return max(peaks)


class TraceWindow:
    """The profiler around a part of the measured window, when ``--trace 1``.
    The trace goes to a directory inside the checkout and is deleted once it
    is reduced."""

    def __init__(self, jax, cell: Cell, on: bool):
        self.jax, self.on, self.open = jax, on, False
        self.dir = os.path.join(OUT, "trace", cell.name)
        self.t0 = self.t1 = None

    def start(self):
        if self.on and not self.open:
            shutil.rmtree(self.dir, ignore_errors=True)
            os.makedirs(self.dir, exist_ok=True)
            self.jax.profiler.start_trace(self.dir)
            self.open = True
            self.t0 = time.perf_counter()

    def stop(self):
        if self.open:
            self.t1 = time.perf_counter()
            self.jax.profiler.stop_trace()
            self.open = False

    def reduce(self, chips: int) -> Optional[Dict]:
        if not self.on or self.t0 is None:
            return None
        from . import trace_reduce
        paths = glob.glob(os.path.join(self.dir, "plugins", "profile", "*",
                                       "*.xplane.pb"))
        if not paths:
            raise RuntimeError("chipbench: the profiler wrote no trace")
        red = trace_reduce.reduce_file(paths[0], chips)
        shutil.rmtree(self.dir, ignore_errors=True)
        return red


def read_per_layer(cell: Cell, record: Dict) -> Dict[str, Dict]:
    """Each per-layer metric of this cell through its own reader. A metric is
    ``chipbench/metrics/<name>.json``; its ``reader`` is ``module:function``
    under ``chipbench/readers/``. A reader that finds nothing to read returns
    None and the metric is left out of the line."""
    out = {}
    for m in cell.per_layer:
        spec = load_json(find("metrics", m["name"] + ".json"))
        mod, fn = spec["reader"].split(":")
        reader: Callable = getattr(
            importlib.import_module(f"chipbench.readers.{mod}"), fn)
        value = reader(record, spec)
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


def emit(cell: Cell, trace: bool, device: Dict, correct: bool, attempted: int,
         failed: int, e2e: Dict[str, float], per_layer: Dict[str, Dict],
         reduced: Optional[Dict], compared: List[Dict], extra: Dict) -> int:
    """Print the compared numbers beside their limits on standard error and
    the one result line on standard output."""
    if trace:
        metrics = per_layer
        device = {**device, "busy_s": reduced["busy_s"],
                  "window_s": reduced["window_s"]}
    else:
        units = {m["name"]: m["unit"] for m in cell.end_to_end}
        metrics = {k: {"value": float(v), "unit": units[k]}
                   for k, v in e2e.items() if k in units}
    line = {"correct": bool(correct) and not cell.rehearsal,
            "attempted": int(attempted), "failed": int(failed),
            "metrics": metrics, "device": device}
    if trace and reduced is not None:
        line["breakdown"] = {"device_ops": reduced["device_ops"][:10],
                             "idle_gaps": reduced["idle_gaps"][:10]}
    line.update(extra)
    if cell.rehearsal:
        line["rehearsal"] = True
        line["rehearsal_correct"] = bool(correct)
    line["compared"] = compared
    for c in compared:
        print(f"chipbench: compared {c['name']}={c['value']:.6g} "
              f"limit={c['limit']:.6g} "
              f"{'ok' if c['value'] <= c['limit'] else 'OVER'}",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(line), flush=True)
    return 0
