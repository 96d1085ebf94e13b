"""One run of one cell: set-up, the measured window through the
configuration's own example driver, recovery, the comparison with the plain
reference, and the result line.

Everything that belongs to one configuration, one traffic mix or one
per-layer metric is data that this file finds by the name in the benchmark
(``BENCHMARK.json``); no list of cells or metrics lives here.  What belongs
to one FAMILY of configurations (one example driver, its state, its seeded
data, its plain reference) sits in the adapter the configuration names.

A configuration's JSON names ``entry`` (the example driver's ``main.py``,
whose ``main(argv)`` runs a ``HierarchyDriver``), ``adapter`` and
``reference`` (paths from the root of the repo).  An adapter is a module
with these names (``load_adapter`` refuses one that lacks any, by name):

``BUILDER``         name, in the entry module, of the function that returns
                    ``(integ, state)``; it is spied, and its state seeded
``SPIED``           ``{key: name}``: further functions of the entry module
                    to time, by the key the readers find them under
                    (``save``, ``restore``); may be empty, and a traffic mix
                    with ``recover`` needs ``restore``
``leaves(state)``   ``{name: array}``: what is checkpoint-compared and
                    handed to the reference
``seed(integ, state, seed, seed_data)``  the run's state from ``--seed``
``reference(module, db, lowp=None)``     the configuration's reference
                    module driven: an object with ``advance(state, steps)``,
                    ``close()`` and ``seconds``
``state_from(module, arrays)``  a reference state from named host arrays
``arrays_from(ref_state)``      the inverse, for the control's output
``compare(ref_out, prog_out, ref_in)``   ``{name: reading}``, matched
                    against the configuration's ``limits`` by name
``faults``          ``{name: fn(state) -> state}`` planted on the built
                    state by tests
``rehearse_keys``   ``{section: {key: value}}`` that ``--rehearse`` sets
``report(integ, db)``  one log line on what resolved; never compared
``grid_n(db)``      ``ctx["grid_n"]`` for the readers
"""

from __future__ import annotations

import contextlib
import importlib.util
import json
import os
import shutil
import sys
import time
from concurrent.futures import ThreadPoolExecutor

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
ADAPTER_NAMES = ("BUILDER", "SPIED", "leaves", "seed", "reference",
                 "state_from", "arrays_from", "compare", "faults",
                 "rehearse_keys", "report", "grid_n")


class WindowClosed(Exception):
    """Raised at a chunk boundary to end ``HierarchyDriver.run``."""


def log(msg: str):
    print(f"[perfbench] {msg}", file=sys.stderr, flush=True)


def load_json(path: str):
    with open(path) as f:
        return json.load(f)


def load_module(path: str, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_adapter(path: str):
    mod = load_module(path, "perfbench_adapter")
    for name in ADAPTER_NAMES:
        if not hasattr(mod, name):
            raise SystemExit(f"perfbench: adapter {path} lacks {name!r}")
    return mod


def find_cell(bench: dict, workload: str):
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise SystemExit(f"unknown workload {workload!r}; "
                         f"the benchmark has {sorted(cells)}")
    cell = cells[workload]
    entry = next(c for c in bench["configs"] if c["name"] == cell["config"])
    config = load_json(os.path.join(ROOT, entry["file"]))
    config["_dir"] = os.path.dirname(os.path.join(ROOT, entry["file"]))
    traffic = load_json(os.path.join(HERE, "traffic",
                                     cell["traffic"] + ".json"))
    return cell, config, traffic


def to_host(adapter, state) -> dict:
    return {k: np.asarray(v) for k, v in adapter.leaves(state).items()}


class _SyncSpy:
    """Stands in for the chunk's health flag so that the driver's one
    ``np.asarray(health)`` per chunk, the host's wait for the device, shows
    in the trace under its own name."""

    def __init__(self, health):
        self._health = health

    def __array__(self, dtype=None, copy=None):
        import jax

        with jax.profiler.TraceAnnotation("bench/sync"):
            out = np.asarray(self._health)
        return out if dtype is None else out.astype(dtype)


class Probe:
    """The benchmark's spies on one ``main()``: it times the chunks and
    the callbacks, holds the last chunk's input and output state for the
    comparison, opens the window after ``warm_steps`` and closes it at the
    first chunk boundary at or after ``seconds`` at which the steps since
    it opened are a multiple of ``period_steps``: every window holds whole
    periods of the traffic's cadences, and so the same host work."""

    def __init__(self, seconds: float, warm_steps: int, trace_chunks: int,
                 trace_dir: str | None, start_step: int = 0,
                 stop_after_chunks: int | None = None, period_steps: int = 1):
        self.seconds, self.warm_steps = seconds, warm_steps
        self.period_steps = period_steps
        self.trace_chunks, self.trace_dir = trace_chunks, trace_dir
        self.stop_after_chunks = stop_after_chunks
        self.step = start_step
        self.phase = "setup"
        self.chunks = []            # closed chunks of the window
        self.open_chunk = None
        self.first_calls = {}       # chunk length -> seconds of first call
        self.calls = {"metrics_fn": [], "viz_fn": [], "checkpoint_fn": []}
        self.window_t0 = self.window_t1 = None
        self.trace_t1 = None        # set when the traced chunks are done
        self.traced = []            # chunks run under the profiler
        self.pair = None            # (state_in, state_out, steps), last chunk
        self.checkpointed = None    # (state, step) of the last checkpoint
        self.first_sync_end = None
        self.n_chunks_seen = 0
        self.failed_chunks = 0
        self.fault = None           # tests plant a fault here

    # -- callbacks ---------------------------------------------------------
    def wrap_callback(self, name, fn):
        import jax

        def wrapped(state, step):
            t0 = time.perf_counter()
            if name == "metrics_fn":
                # the first thing the driver does after a chunk's sync
                if self.first_sync_end is None:
                    self.first_sync_end = t0
            if name == "checkpoint_fn":
                self.checkpointed = (state, step)
            with jax.profiler.TraceAnnotation(f"bench/{name}"):
                out = fn(state, step)
            if self.phase != "setup":
                self.calls[name].append((t0, time.perf_counter() - t0))
            return out
        return wrapped

    # -- chunk boundaries --------------------------------------------------
    def boundary(self, driver, n: int, fn):
        """Called by the driver subclass where ``run`` asks for the chunk
        program: the start of a chunk and the end of the one before."""
        import jax

        now = time.perf_counter()
        oc = self.open_chunk
        if oc is not None:
            oc["t_end"] = now
            oc["wall_s"] = driver.last_chunk_wall_s
            if self.phase == "window":
                self.chunks.append(oc)
            elif self.phase == "trace":
                self.traced.append(oc)
            self.open_chunk = None
        self.n_chunks_seen += 1
        if (self.stop_after_chunks is not None
                and self.n_chunks_seen > self.stop_after_chunks):
            raise WindowClosed
        if self.phase == "setup" and self.step >= self.warm_steps:
            self.phase, self.window_t0 = "window", now
            self.window_step0 = self.step
        if (self.phase == "window" and now - self.window_t0 >= self.seconds
                and (self.step - self.window_step0) % self.period_steps == 0):
            self.window_t1 = now
            if not self.trace_chunks:
                raise WindowClosed
            self.phase = "trace"
            os.makedirs(self.trace_dir, exist_ok=True)
            jax.profiler.start_trace(self.trace_dir)
        if self.phase == "trace" and len(self.traced) >= self.trace_chunks:
            self.trace_t1 = time.perf_counter()
            jax.profiler.stop_trace()
            self.phase = "done"
            raise WindowClosed
        self.open_chunk = {"t_start": now, "steps": n}
        self.step += n
        first = n not in self.first_calls
        if not self.first_calls:
            self.t_first_call = now

        def call(state, *args):
            t0 = time.perf_counter()
            with jax.profiler.TraceAnnotation("bench/dispatch"):
                if self.fault == "state_unchanged":
                    _, health = fn(state, *args)
                    out = state
                else:
                    out, health = fn(state, *args)
            if first:
                self.first_calls[n] = time.perf_counter() - t0
            self.pair = (state, out, n)
            return out, _SyncSpy(health)
        return call


def install(mod, probe: Probe, adapter, seed_fn=None):
    """Put the spies into the loaded ``main.py`` module: a driver subclass
    that reports chunk boundaries, the adapter's ``SPIED`` functions timed
    (checkpoint write/restore), and the seeded state in place of the built
    one."""
    import jax

    base = getattr(mod.HierarchyDriver, "_bench_base", mod.HierarchyDriver)

    class BenchDriver(base):
        _bench_base = base

        def __init__(self, integ, cfg, **kw):
            for name in probe.calls:
                if kw.get(name) is not None:
                    kw[name] = probe.wrap_callback(name, kw[name])
            super().__init__(integ, cfg, **kw)
            probe.driver = self

        def _chunk(self, n):
            return probe.boundary(self, n, super()._chunk(n))

    mod.HierarchyDriver = BenchDriver
    for name in (adapter.BUILDER, *adapter.SPIED.values()):
        if not hasattr(mod, "_bench_" + name):
            setattr(mod, "_bench_" + name, getattr(mod, name))
    probe.spied = {"build": [], **{key: [] for key in adapter.SPIED}}

    def timed(key, fn, label):
        def wrapped(*a, **kw):
            t0 = time.perf_counter()
            with jax.profiler.TraceAnnotation(label):
                out = fn(*a, **kw)
            probe.spied[key].append((t0, time.perf_counter() - t0))
            return out
        return wrapped

    def build(*a, **kw):
        integ, state = getattr(mod, "_bench_" + adapter.BUILDER)(*a, **kw)
        probe.integ = integ
        if seed_fn is not None:
            state = seed_fn(integ, state)
        if probe.fault in adapter.faults:
            state = adapter.faults[probe.fault](state)
        return integ, state

    setattr(mod, adapter.BUILDER, timed("build", build, "bench/build"))
    for key, name in adapter.SPIED.items():
        setattr(mod, name, timed(key, getattr(mod, "_bench_" + name),
                                 "bench/" + name))


def run_main(mod, argv, logfile):
    """``main(argv)`` until the probe closes the window.  Returns the
    exception that ended it early, or None."""
    from ibamr_tpu.utils.hierarchy_driver import SimulationDiverged

    with open(logfile, "a") as lf, contextlib.redirect_stdout(lf):
        try:
            mod.main(argv)
        except WindowClosed:
            return None
        except SimulationDiverged as e:
            return e
    raise RuntimeError("main() returned before the window closed: "
                       "num_steps is too small")


# -- the comparison that decides ``correct`` -----------------------------

def check_chunk(adapter, module, db, pair_host, label, lowp=None):
    """Advance the configuration's plain reference (``module``) over one
    chunk from the state the timed path started it from; returns ``{name:
    reading}``.  With ``lowp`` also the control's readings
    (``control.<label>.<name>``): the reference in the lower precision, put
    in the program's place."""
    s_in, s_out, steps = pair_host
    t0 = time.perf_counter()
    ref = adapter.reference(module, db)
    r_in = adapter.state_from(module, s_in)
    r_out = ref.advance(r_in, steps)
    ref.close()
    out = {f"{label}.{k}": v
           for k, v in adapter.compare(r_out, s_out, r_in).items()}
    log(f"reference {label}: {steps} steps in "
        f"{time.perf_counter() - t0:.1f} s "
        f"{ {k: round(v, 1) for k, v in ref.seconds.items()} }")
    if lowp is not None:
        low = adapter.reference(module, db, lowp=lowp)
        l_out = low.advance(r_in, steps)
        low.close()
        got = adapter.compare(r_out, adapter.arrays_from(l_out), r_in)
        out.update({f"control.{label}.{k}": v for k, v in got.items()})
    return out


# -- one run -----------------------------------------------------------------

def run(args, t_proc0: float, require_chip: bool = True, fault=None,
        bench: dict | None = None):
    """``bench`` is the benchmark as a dict (default: ``BENCHMARK.json``),
    so that a test can run a configuration that is in no benchmark."""
    from perfbench import inputfile

    if bench is None:
        bench = load_json(os.path.join(ROOT, "BENCHMARK.json"))
    cell, config, traffic = find_cell(bench, args.workload)
    adapter = load_adapter(os.path.join(ROOT, config["adapter"]))
    if traffic.get("recover") and "restore" not in adapter.SPIED:
        raise SystemExit(f"perfbench: traffic {traffic['name']!r} recovers, "
                         f"and {config['adapter']} spies no 'restore'")
    rehearse = getattr(args, "rehearse", False)
    out = os.path.join(ROOT, "perfbench_out", cell["name"])
    shutil.rmtree(out, ignore_errors=True)
    os.makedirs(out)

    # ---- the input file of this run: the configuration's, with the
    # traffic mix's keys, the run's paths and an unreachable num_steps
    text = open(os.path.join(config["_dir"], config["input_file"])).read()
    keys = {s: dict(kv) for s, kv in traffic["set"].items()}
    keys.setdefault("Main", {}).update(
        log_file=f"{out}/metrics.jsonl", viz_dirname=f"{out}/viz",
        restart_dirname=f"{out}/restart")
    keys.setdefault("INSStaggeredHierarchyIntegrator", {})[
        "num_steps"] = 100_000_000
    if rehearse:
        for section, kv in adapter.rehearse_keys.items():
            keys.setdefault(section, {}).update(kv)
    text = inputfile.set_keys(text, keys)
    db = inputfile.parse(text)
    inp = os.path.join(out, "input3d")
    with open(inp, "w") as f:
        f.write(text)

    # ---- backend: main.py's own guard raises unless it finds a TPU
    mod = load_module(os.path.join(ROOT, config["entry"]), "perfbench_entry")
    t_loaded = time.perf_counter()
    import jax
    import jax.monitoring

    # every program of the run goes into the persistent cache, the small
    # ones too (the program's own threshold of 2 s would leave some sixty
    # of them to compile again in every run)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    # and stays there: a 256^3 chunk program is 113 MiB in the cache, and
    # where the machine caps the cache at 192 MiB (JAX_COMPILATION_CACHE_
    # MAX_SIZE) a cell with two of them compiles one again in EVERY run and
    # once more in the recovery (measured: setup_s 222 s, recover_s 141 s)
    jax.config.update("jax_compilation_cache_max_size", 2 ** 30)

    devs = jax.devices()
    dev = devs[0]
    if require_chip and not rehearse and (
            dev.platform != "tpu" or len(devs) < cell["chips"]):
        raise SystemExit(f"perfbench: needs {cell['chips']} TPU chip(s); "
                         f"found {len(devs)} x {dev.platform}")
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devs)}
    log(f"cell {cell['name']} seed {args.seed} seconds {args.seconds} "
        f"trace {args.trace} device {device}")

    compiles, cache_hits = [], []
    jax.monitoring.register_event_duration_secs_listener(
        lambda name, secs, **kw: compiles.append(
            (time.perf_counter(), secs))
        if name.endswith("backend_compile_duration")
        else cache_hits.append(secs)
        if name.endswith("cache_retrieval_time_sec") else None)

    def seed_fn(integ, state):
        return adapter.seed(integ, state, args.seed, config["seed_data"])

    trace_dir = os.path.join(out, "trace")
    probe = Probe(args.seconds, traffic["warm_steps"],
                  traffic["trace_chunks"] if args.trace else 0, trace_dir,
                  period_steps=traffic["period_steps"])
    probe.fault = fault
    install(mod, probe, adapter, seed_fn)
    logfile = os.path.join(out, "program.log")
    err = run_main(mod, ["main.py", inp], logfile)
    if err is not None:
        probe.failed_chunks += 1
        log(f"window ended by {type(err).__name__}: {err}")
    t_end = probe.window_t1 or time.perf_counter()
    if probe.window_t0 is None:
        raise RuntimeError("the window never opened")
    setup_s = probe.window_t0 - t_proc0
    chunks = probe.chunks
    steps = sum(c["steps"] for c in chunks)
    window_s = t_end - probe.window_t0
    in_window = [c for c in compiles if probe.window_t0 <= c[0] <= t_end]
    in_win = {name: sum(probe.window_t0 <= t <= t_end for t, _ in calls)
              for name, calls in probe.calls.items()}
    log(f"setup_s {setup_s:.3f} window_s {window_s:.3f} chunks "
        f"{len(chunks)} steps {steps} periods of {probe.period_steps}: "
        f"{steps / probe.period_steps:g} dumps {in_win['viz_fn']} "
        f"checkpoints {in_win['checkpoint_fn']} lengths "
        f"{sorted({c['steps'] for c in chunks})} first_calls "
        f"{probe.first_calls} compiles_in_window {len(in_window)} "
        f"compile_events {len(compiles)} cache_reads {len(cache_hits)}")
    log(f"set-up: main.py loaded (imports, backend) "
        f"{t_loaded - t_proc0:.2f} s; build+seed "
        f"{probe.spied['build'][0][1]:.2f} s; first chunk call at "
        f"{probe.t_first_call - t_proc0:.2f} s; window at {setup_s:.2f} s; "
        f"compile seconds in set-up "
        f"{sum(c[1] for c in compiles if c[0] < probe.window_t0):.2f}")
    log(adapter.report(probe.integ, db))

    # ---- recovery: restore the last checkpoint of the window into a new
    # driver, as ``main.py <input> <restart_dir> <step>`` does
    recover = None
    pairs = {"window": probe.pair}
    saved = probe.checkpointed
    if traffic.get("recover") and saved is not None and err is None:
        saved_host = to_host(adapter, saved[0])
        rprobe = Probe(0.0, 10 ** 12, 0, None, start_step=saved[1],
                       stop_after_chunks=1)
        rprobe.fault = fault
        install(mod, rprobe, adapter)
        t0 = time.perf_counter()
        rerr = run_main(mod, ["main.py", inp, f"{out}/restart",
                              str(saved[1])], logfile)
        r0, rs = rprobe.spied["restore"][0]
        recover = {"recover_s": rprobe.first_sync_end - r0, "restore_s": rs,
                   "main_s": time.perf_counter() - t0,
                   "first_call_s": list(rprobe.first_calls.values())[0]}
        if rerr is not None:
            probe.failed_chunks += 1
        restored = to_host(adapter, rprobe.pair[0])
        if fault == "restore_altered":
            first = next(iter(restored))
            restored[first] = restored[first] * (1 + 1e-6)
        pairs["recover"] = rprobe.pair
        recover["restore_mismatch"] = float(max(
            np.max(np.abs(np.asarray(restored[k], np.float64)
                          - np.asarray(saved_host[k], np.float64)))
            for k in saved_host))
        log(f"recover {recover}")
        del rprobe, restored, saved_host
    stats = dev.memory_stats() or {}
    device["memory_peak_bytes"] = int(stats.get("peak_bytes_in_use", 0))
    op_names = None
    if args.trace and probe.trace_t1 is not None:
        # what each instruction of the chunk programs computes: the
        # compiled text's op_name (a cache read, after the peak is taken)
        from perfbench import tracereduce

        t0 = time.perf_counter()
        op_names = {}
        for n, fn in probe.driver._chunks.items():
            text = fn.lower(probe.pair[0], probe.driver.cfg.dt) \
                .compile().as_text()
            op_names.update(tracereduce.op_names_from_hlo(text))
            del text
        log(f"op names of {len(op_names)} instructions read in "
            f"{time.perf_counter() - t0:.1f} s")

    # ---- pull the compared chunks to the host, free the device state
    pairs_host = {}
    for label, pr in pairs.items():
        if pr is not None:
            s_out = to_host(adapter, pr[1])
            if fault == "answer_altered":
                first = next(iter(s_out))
                s_out[first] = s_out[first] + 0.02 * np.roll(s_out[first],
                                                             7, 0)
            pairs_host[label] = (to_host(adapter, pr[0]), s_out, pr[2])
    trace = None
    if args.trace and probe.trace_t1 is not None:
        from perfbench import tracereduce

        t0 = time.perf_counter()
        try:
            trace = tracereduce.reduce_dir(
                trace_dir, steps=sum(c["steps"] for c in probe.traced),
                sample_to=getattr(args, "trace_sample", None),
                op_names=op_names)
        except ValueError as e:
            if not rehearse:      # the CPU's trace has no device plane
                raise
            log(f"rehearsal: {e}")
        if trace is not None:
            log(f"trace reduced in {time.perf_counter() - t0:.1f} s: busy_s "
                f"{trace['busy_s']:.4f} window_s {trace['window_s']:.4f} "
                f"classes {trace['op_class_s']}")
        shutil.rmtree(trace_dir, ignore_errors=True)
    del pairs, saved, mod
    probe.pair = probe.checkpointed = None
    probe.driver = probe.integ = None

    # ---- correct: every compared number under its limit
    limits = config["limits"]
    lowp = getattr(args, "control", None)
    # the chunks' references side by side: each leaves most of the host's
    # cores idle in its single-threaded parts (numpy releases the GIL)
    readings = {}
    module = load_module(os.path.join(ROOT, config["reference"]),
                         "perfbench_reference")
    with ThreadPoolExecutor(max(1, len(pairs_host))) as pool:
        for got in pool.map(
                lambda item: check_chunk(adapter, module, db, item[1],
                                         item[0], lowp=lowp),
                pairs_host.items()):
            readings.update(got)
    if recover is not None:
        readings["recover.restore_mismatch"] = recover["restore_mismatch"]
    compared, control = {}, {}
    for name, val in readings.items():
        lim = limits.get(name.rsplit(".", 1)[1])
        if lim is not None:
            (control if name.startswith("control.") else compared)[name] = {
                "value": val, "limit": lim}
    attempted = len(chunks) + (1 if err is not None else 0)
    correct = (bool(compared) and err is None and all(
        np.isfinite(c["value"]) and c["value"] <= c["limit"]
        for c in compared.values()))

    # ---- metrics
    ctx = {"cell": cell, "config": config, "traffic": traffic,
           "grid_n": adapter.grid_n(db),
           "window_s": window_s, "steps": steps, "chunks": chunks,
           "setup_s": setup_s, "first_calls": probe.first_calls,
           "calls": probe.calls, "spied": probe.spied,
           "recover": recover, "trace": trace, "device": device,
           "peaks": load_json(os.path.join(HERE, "peaks.json"))}
    metrics = {}
    if args.trace:
        for m in bench["per_layer"]:
            if "workloads" in m and cell["name"] not in m["workloads"]:
                continue
            reader = load_module(os.path.join(HERE, "metrics",
                                              m["name"] + ".py"),
                                 "metric_" + m["name"].replace(".", "_"))
            val = reader.read(ctx)
            if val is not None:
                metrics[m["name"]] = {"value": float(val), "unit": m["unit"]}
    else:
        e2e = end_to_end(ctx)
        for m in bench["end_to_end"]:
            if "workloads" in m and cell["name"] not in m["workloads"]:
                continue
            metrics[m["name"]] = {"value": float(e2e[m["name"]]),
                                  "unit": m["unit"]}
    result = {"correct": correct, "attempted": attempted,
              "failed": probe.failed_chunks, "metrics": metrics,
              "device": device}
    if trace is not None:
        device["busy_s"], device["window_s"] = \
            trace["busy_s"], trace["window_s"]
        result["breakdown"] = {"device_ops": trace["device_ops"][:10],
                               "idle_gaps": trace["idle_gaps"][:10]}
    if control:
        over = [k for k, c in control.items() if c["value"] > c["limit"]]
        log(f"control {lowp}: fails {over or 'NOTHING'}")
    others = {k: v for k, v in readings.items() if k not in compared}
    log(f"read but not compared: {json.dumps(others)}")
    for name, c in compared.items():
        log(f"compared {name} = {c['value']:.6g} (limit {c['limit']:g})")
    log(f"correct {correct}")
    result["compared"] = compared
    if control:
        result["control"] = control
    return result


def end_to_end(ctx) -> dict:
    """The end-to-end metrics, from the host clock alone.  ``step_ms`` is
    the whole window over all its steps."""
    out = {"setup_s": ctx["setup_s"],
           "step_ms": 1e3 * ctx["window_s"] / ctx["steps"]}
    if ctx["recover"] is not None:
        out["recover_s"] = ctx["recover"]["recover_s"]
    return out
