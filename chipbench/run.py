"""Run one cell of the on-chip benchmark once.

    python3 chipbench/run.py --workload ml1m.read --seed 7 --seconds 20 --trace 0

The cell is read from ``BENCHMARK.json`` at the root of the checkout; its
configuration from ``chipbench/configs/<config>.json``, its traffic mix
from ``chipbench/traffic/<traffic>.json``, its offered load from
``chipbench/cells/<workload>.json`` and each per-layer metric's reader from
``chipbench/metrics/<metric>.py``. Nothing here knows a cell by name.

A run builds the deployment from ``--seed`` (ratings, fit, bucketed
serving state), warms the programs of the cell's own batch shapes and
kinds, drives ``RequestEngine`` with the traffic for ``--seconds``, waits
for every request due in the window, and checks what was served against
the float64 reference (``chipbench/reference.py``). With ``--trace 0`` it
reports the cell's end-to-end metrics; with ``--trace 1`` it records engine
spans and a profiler capture and reports the per-layer metrics instead.

The last line of standard output is one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics``, ``device`` (and ``breakdown`` when
traced), and last ``checks``, each number compared beside its limit. The
same numbers are the last lines of standard error. Without a TPU, or with
fewer chips than the cell asks for, it exits 2 and prints no result.
"""
from __future__ import annotations

import time

T_START = time.monotonic()

import argparse  # noqa: E402
import copy  # noqa: E402
import gc  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import statistics  # noqa: E402
import sys  # noqa: E402
import threading  # noqa: E402
import traceback  # noqa: E402
import types  # noqa: E402
from pathlib import Path  # noqa: E402

import numpy as np  # noqa: E402

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
RUNS_DIR = ROOT / ".bench_runs"
CACHE_DIR = RUNS_DIR / "jax_cache"
DRAIN_S = 60.0          # how long a request due in the window may take
TRACE_AT = 0.4          # the capture starts this far into the window
TRACE_S = 2.0           # and lasts this long
KEEP_GENERATIONS = 256  # write-cell generations whose reads are checked
COLLECTIVES = ("all-reduce", "all-gather", "collective-permute",
               "reduce-scatter", "all-to-all")  # HLO names, logged when traced
for p in (str(ROOT), str(ROOT / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)
# the TPU runtime would otherwise log to a fixed path under /tmp
os.environ.setdefault("TPU_LOG_DIR", "disabled")

from chipbench import data as datalib  # noqa: E402
from chipbench import reference as reflib  # noqa: E402
from chipbench import traffic as trafficlib  # noqa: E402


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def load_cell(workload: str) -> types.SimpleNamespace:
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise SystemExit(f"unknown workload {workload!r}; "
                         f"known: {sorted(cells)}")
    cell = cells[workload]
    cfg = json.loads((BENCH / "configs" / f"{cell['config']}.json")
                     .read_text())
    mix = json.loads((BENCH / "traffic" / f"{cell['traffic']}.json")
                     .read_text())
    load_file = BENCH / "cells" / f"{workload}.json"
    load = json.loads(load_file.read_text()) if load_file.exists() else {}

    def applies(m):
        return "workloads" not in m or workload in m["workloads"]

    return types.SimpleNamespace(
        name=workload, cell=cell, cfg=cfg, mix=mix, load=load,
        end_to_end=[m for m in bench["end_to_end"] if applies(m)],
        per_layer=[m for m in bench["per_layer"] if applies(m)])


def use_compile_cache() -> None:
    """Every executable goes to the checkout's cache, however fast it
    compiled, so that only a cell's first run in a checkout compiles."""
    import jax

    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(CACHE_DIR)
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


class CompileCounter:
    """Traces and backend compiles (cache hits included), as JAX reports
    them; the window should see none."""

    def __init__(self):
        from jax._src import dispatch, monitoring

        self.names = (dispatch.JAXPR_TRACE_EVENT,
                      dispatch.BACKEND_COMPILE_EVENT)
        self.count = 0
        monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **_):
        if event in self.names:
            self.count += 1


class GcPauses:
    """Durations of the full collections while it is installed."""

    def __init__(self):
        self.gen2, self._t = [], None
        gc.callbacks.append(self._on)

    def _on(self, phase, info):
        if info["generation"] != 2:
            return
        if phase == "start":
            self._t = time.perf_counter()
        elif self._t is not None:
            self.gen2.append(time.perf_counter() - self._t)

    def stop(self) -> None:
        gc.callbacks.remove(self._on)


class Stalls:
    """Oversleeps of a thread that sleeps 5 ms at a time: the moments the
    process stood still. On the chip's machine it stalls ~0.1 s every
    5-20 s and, at random, for seconds; a run that reads far off shows why
    here.

    Each stall of 50 ms or more is logged on stderr (nothing of it enters
    the result line) with what tells its causes apart:

    - ``witness``: whether a second process, which sleeps 5 ms at a time
      too, overslept over the same stretch (the host held both back) or
      ran on (this process alone stood still);
    - ``cpu``: the process's CPU seconds over it, and ``threads`` each
      Python thread's: a Python thread that ran through it held the
      interpreter lock;
    - ``ticks``: the host's CPU ticks (all kinds, all CPUs, ``/proc/stat``)
      over it, beside ``tick_rate``, their rate over ordinary stretches;
    - ``stacks``: every Python thread's stack as this thread finds them
      when it runs again. A ``faulthandler`` watchdog would catch them
      inside the stall, but it reads the threads without the lock and
      crashed the process."""

    STEP = 0.005
    LOGGED = 0.05
    RATE = 0.1  # the stretch of one tick-rate reading
    WITNESS = (
        "import os, time\n"
        "parent, step, t = os.getppid(), %r, time.monotonic()\n"
        "while os.getppid() == parent:\n"
        "    time.sleep(step)\n"
        "    now = time.monotonic()\n"
        "    if now - t - step > %r:\n"
        "        print(t, now - t - step, flush=True)\n"
        "    t = now\n")

    def __init__(self):
        import subprocess

        self.seen, self.tick_rate = [], []
        self._stop = threading.Event()
        self._stat = os.open("/proc/stat", os.O_RDONLY)
        self.witness = subprocess.Popen(
            [sys.executable, "-c", self.WITNESS % (self.STEP, self.LOGGED)],
            stdout=subprocess.PIPE)
        os.set_blocking(self.witness.stdout.fileno(), False)
        self._held = b""
        self.thread = threading.Thread(target=self._watch, name="stalls",
                                       daemon=True)
        self.thread.start()

    def _ticks(self) -> bytes:
        """The first line of ``/proc/stat``, parsed only for a stall."""
        return os.pread(self._stat, 256, 0).split(b"\n", 1)[0]

    @staticmethod
    def _clocks() -> dict:
        """Each live Python thread's CPU clock, by name: Linux's id of a
        thread's scheduler CPU clock, made from its thread id as the
        kernel's ``MAKE_THREAD_CPUCLOCK`` does (a thread gone since then
        reads as no clock, never as another's)."""
        return {th.name: (~th.native_id << 3) | 6
                for th in threading.enumerate() if th.native_id}

    @staticmethod
    def _cpu(clocks: dict) -> dict:
        out = {}
        for name, clk in clocks.items():
            try:
                out[name] = time.clock_gettime(clk)
            except OSError:
                pass
        return out

    def _watch(self) -> None:
        def total(line: bytes) -> int:
            return sum(int(v) for v in line.split()[1:])

        clocks = self._clocks()
        t = rated = time.monotonic()
        cpu, threads, ticks = (time.process_time(), self._cpu(clocks),
                               self._ticks())
        ticks_rated = ticks
        while not self._stop.wait(self.STEP):
            now, cpu_now, threads_now, ticks_now = (
                time.monotonic(), time.process_time(), self._cpu(clocks),
                self._ticks())
            lost = now - t - self.STEP
            if lost > self.LOGGED:
                frames = sys._current_frames()
                names = {th.ident: th.name for th in threading.enumerate()}
                self.seen.append(types.SimpleNamespace(
                    t=t, at=t - T_START, lost=lost, cpu=cpu_now - cpu,
                    threads={k: round(v - threads[k], 4)
                             for k, v in threads_now.items() if k in threads},
                    ticks=total(ticks_now) - total(ticks),
                    stacks={names.get(k, k): traceback.format_stack(f)[-4:]
                            for k, f in frames.items()}))
                rated, ticks_rated = now, ticks_now
            elif now - rated >= self.RATE:
                self.tick_rate.append((total(ticks_now) - total(ticks_rated))
                                      * self.RATE / (now - rated))
                rated, ticks_rated = now, ticks_now
                clocks = self._clocks()
                threads_now = self._cpu(clocks)
            t, cpu, threads, ticks = now, cpu_now, threads_now, ticks_now

    def stop(self) -> None:
        self._stop.set()
        self.thread.join()
        self.witness.kill()
        self.witness.wait()
        self.witness.stdout.close()
        os.close(self._stat)

    def _witnessed(self) -> list:
        """The witness's oversleeps so far: (start, seconds lost)."""
        try:
            self._held += os.read(self.witness.stdout.fileno(), 1 << 16)
        except BlockingIOError:
            pass
        return [tuple(map(float, ln.split()))
                for ln in self._held.decode().splitlines()]

    def report(self, t_window: float, seconds: float) -> None:
        """Each stall on stderr: when, how long, its readings and, inside
        the window, the stacks found after it."""
        q = (statistics.quantiles(self.tick_rate, n=4)
             if len(self.tick_rate) > 1 else [])
        log(f"stalls: window {t_window:.3f}-{t_window + seconds:.3f} s "
            f"after start; host ticks per {self.RATE} s elsewhere: "
            f"quartiles {[round(v, 1) for v in q]} over "
            f"{len(self.tick_rate)} stretches")
        held = self._witnessed()
        for s in self.seen:
            inside = t_window <= s.at < t_window + seconds
            s.witness = any(w0 < s.t + self.STEP + s.lost and s.t < w0 + d
                            for w0, d in held)
            log(f"stall {s.at:.3f} s +{s.lost * 1e3:.0f} ms "
                f"({'in' if inside else 'outside'} the window): witness "
                f"{'stalled too' if s.witness else 'ran on'}; process cpu "
                f"{s.cpu:.3f} s, by Python thread {json.dumps(s.threads)}, "
                f"other threads {s.cpu - sum(s.threads.values()):.3f} s; "
                f"host ticks {s.ticks}")
            for name, stack in (s.stacks.items() if inside else ()):
                log(f"  | {name}: "
                    + " < ".join(ln.strip().splitlines()[0]
                                 for ln in reversed(stack)))


# ------------------------------------------------------------- deployment
def build(c, seed: int):
    """Data, fit and the served backend, as the configuration's
    ``serving.backend`` says; returns (backend, data, spec, timings)."""
    backend = c.cfg["serving"]["backend"]
    if backend == "MutableLocalBackend":
        return build_local(c, seed)
    if backend == "MutableShardedBackend":
        return build_sharded(c, seed)
    raise SystemExit(f"chipbench: no build for serving.backend {backend!r}")


def build_local(c, seed: int):
    """One device: the dense matrix, ``fit``, the bucketed state and
    ``MutableLocalBackend``."""
    import jax
    import jax.numpy as jnp

    from chipbench.served import Served, program_view
    from repro.configs import registry
    from repro.core import RatingMatrix, fit
    from repro.lifecycle import buckets
    from repro.serving import MutableLocalBackend

    t = {}
    t0 = time.monotonic()
    ratings = datalib.synthesize(c.cfg, seed)
    t["data"] = time.monotonic() - t0
    t0 = time.monotonic()
    u, p = ratings.n_users, ratings.n_items
    dense = jax.jit(lambda i, j, v: jnp.zeros((u, p), jnp.float32)
                    .at[i, j].set(v))(ratings.users, ratings.items,
                                      ratings.values)
    spec = registry.get(c.cfg["model"]).model
    st = fit(jax.random.PRNGKey(seed & 0x7FFFFFFF),
             RatingMatrix(dense, u, p), spec,
             backend=c.cfg["fit_graph_backend"])
    jax.block_until_ready(st.graph.weights)
    t["fit"] = time.monotonic() - t0
    t0 = time.monotonic()
    cap = c.cfg["serving"]["capacity"]
    bst = buckets.from_state(st, min_bucket=cap)
    del st, dense
    inner = MutableLocalBackend(bst, spec, min_bucket=cap)
    jax.block_until_ready(inner.snapshot()[0].bstate.state.ratings)
    t["state"] = time.monotonic() - t0
    return Served(inner, program_view), ratings, spec, t


def build_sharded(c, seed: int):
    """The cell's chips as one ``pod`` mesh, built as the program's engine
    mode builds it: ``fit_distributed``, the state block-partitioned by
    ``buckets.from_state_sharded`` and ``MutableShardedBackend`` over the
    logical id -> (shard, slot) tables. The ratings go to the fit as a host
    array: JAX shards no array whose rows the chips do not divide, and
    neither MovieLens user count is a multiple of 4, so the harness places
    no whole (U, P) matrix on a device itself."""
    import jax

    from chipbench.served import Served, sharded_view
    from repro.configs import registry
    from repro.core.landmark_cf import fit_distributed
    from repro.launch.mesh import make_mesh
    from repro.lifecycle import buckets
    from repro.serving import MutableShardedBackend

    if c.cfg["fit_graph_backend"] != "streaming":
        # fit_distributed has no other graph build
        raise SystemExit("chipbench: a MutableShardedBackend deployment "
                         "fits its graph on the streaming path only; "
                         f"fit_graph_backend is {c.cfg['fit_graph_backend']!r}")
    chips = c.cell["chips"]
    t = {}
    t0 = time.monotonic()
    ratings = datalib.synthesize(c.cfg, seed)
    u, p = ratings.n_users, ratings.n_items
    dense = np.zeros((u, p), np.float32)
    dense[ratings.users, ratings.items] = ratings.values
    t["data"] = time.monotonic() - t0
    t0 = time.monotonic()
    mesh = make_mesh((chips,), ("pod",), devices=jax.devices()[:chips])
    spec = registry.get(c.cfg["model"]).model
    st = fit_distributed(jax.random.PRNGKey(seed & 0x7FFFFFFF), dense, spec,
                         mesh, user_axes=("pod",))
    jax.block_until_ready(st.graph.weights)
    t["fit"] = time.monotonic() - t0
    t0 = time.monotonic()
    # the engine mode's per-shard bucket: the deployment's capacity split
    # over the shards (launch/serve.py)
    cap = max(8, c.cfg["serving"]["capacity"] // chips)
    sst = buckets.from_state_sharded(st, mesh, ("pod",), cap)
    del st, dense
    u_per = -(-u // chips)
    ids = np.arange(u)
    inner = MutableShardedBackend(sst, (ids // u_per).astype(np.int32),
                                  (ids % u_per).astype(np.int32), spec,
                                  min_bucket=cap)
    jax.block_until_ready(inner.snapshot()[0].sstate.state.ratings)
    t["state"] = time.monotonic() - t0
    return Served(inner, sharded_view), ratings, spec, t


def batch_shapes(engine_cfg, mix: dict):
    """The padded batch shapes the cell's reads can form."""
    lo = min(m["rows"][0] for m in mix["mix"] if m["kind"] in
             trafficlib.READS)
    return [s for s in engine_cfg.batch_shapes()
            if s >= engine_cfg.pad_shape(lo)]


def warm(backend, engine_cfg, mix: dict, n_items: int) -> None:
    """Compile (or load from the cache) each program the window runs, on
    the live state. Reads go to the live generation; writes go to a shallow
    copy of the backend, whose publishes leave the live one as it was."""
    import jax

    inner = backend.inner
    kinds = {m["kind"] for m in mix["mix"]}
    pub = inner.snapshot()
    for s in batch_shapes(engine_cfg, mix):
        z = np.zeros(s, np.int64)
        if "pair" in kinds:
            jax.block_until_ready(inner.predict_pairs(pub, z, z))
        if "topn" in kinds:
            jax.block_until_ready(inner.recommend_topn(pub, z,
                                                       engine_cfg.topn))
    if kinds & set(trafficlib.WRITES):
        twin = copy.copy(inner)
        row = np.zeros((1, n_items), np.float32)
        one = np.array([1])
        if "update" in kinds:
            twin.apply_update(one, row)
        if "remove" in kinds:
            twin.apply_remove(one)
        if "fold" in kinds:
            twin.fold_in(row, engine_cfg.fold_bq)
        assert inner.snapshot() is pub, "warm-up published on the live state"


# ----------------------------------------------------------------- window
class Tracing:
    """A profiler capture of ``TRACE_S`` seconds inside the window."""

    def __init__(self, out_dir: Path):
        self.dir = out_dir
        self.error = None

    def start(self, t_window: float, seconds: float) -> None:
        def go():
            import jax

            try:
                time.sleep(max(0.0, t_window + TRACE_AT * seconds
                               - time.monotonic()))
                opts = jax.profiler.ProfileOptions()
                opts.python_tracer_level = 0  # it would trace every call
                jax.profiler.start_trace(str(self.dir),
                                         profiler_options=opts)
                time.sleep(TRACE_S)
                jax.profiler.stop_trace()
            except Exception as e:  # reported, and the run has no trace
                self.error = repr(e)

        self.thread = threading.Thread(target=go, name="trace")
        self.thread.start()


def open_window(engine, schedule, t0: float):
    """Submit each request at its due time; returns (handles, late)."""
    handles = []
    late = np.zeros(len(schedule))
    time.sleep(max(0.0, t0 - time.monotonic()))
    for j, r in enumerate(schedule):
        due = t0 + r.due
        wait = due - time.monotonic()
        if wait > 0:
            time.sleep(wait)
        h = engine.submit(r.kind, users=r.users, items=r.items, rows=r.rows)
        late[j] = time.monotonic() - due
        handles.append((r, due, h))
    return handles, late


def closed_window(engine, order, mix: dict, t0: float, seconds: float):
    """``clients`` callers, each walking its share of ``order``."""
    spec = mix["mix"][0]
    rows = spec["rows"][0]
    clients = mix["clients"]
    handles = [[] for _ in range(clients)]
    t_end = t0 + seconds

    def client(c):
        j = c
        n_chunks = len(order) // rows
        while True:
            now = time.monotonic()
            if now >= t_end:
                return
            lo = (j % n_chunks) * rows
            users = order[lo:lo + rows]
            h = engine.submit(spec["kind"], users=users)
            handles[c].append((users, max(now, t0), h))
            if h is not None:
                h.done.wait(DRAIN_S)
            j += clients

    time.sleep(max(0.0, t0 - time.monotonic()))
    threads = [threading.Thread(target=client, args=(c,), name=f"client{c}")
               for c in range(clients)]
    for th in threads:
        th.start()
    for th in threads:
        th.join()
    return [h for hs in handles for h in hs]


def quantile(values, q: float) -> float:
    """Nearest-rank quantile; a request that failed counts as infinitely
    late."""
    v = np.sort(np.asarray(values, np.float64))
    return float(v[int(np.ceil(q * len(v))) - 1])


def p95(values) -> float:
    return quantile(values, 0.95)


# ------------------------------------------------------------------ checks
def check(dep, served_reads, writes, rng, extra_rows=()):
    """Compare what was served with the reference; returns the Comparison
    and the reference as it stands after the last write."""
    from chipbench.served import state_arrays

    ratings, backend = dep.ratings, dep.backend
    ref = reflib.Reference(ratings.users, ratings.items, ratings.values,
                           ratings.n_users, ratings.n_items,
                           dep.spec.n_landmarks)
    comp = reflib.Comparison()
    final = backend.inner.snapshot()
    views = dict(backend.kept)
    views[final[-1]] = backend.view(final)
    applied = 0
    for g in sorted(views):
        while applied < g:
            kind, user, row = writes[applied]
            ref.apply(kind, user, row)
            applied += 1
        served = state_arrays(views[g])
        if not comp.state(ref, served):
            continue
        at_g = [r for r in served_reads if r["gen"] == g]
        if at_g:
            comp.reads(ref, served, at_g)
        if g == final[-1] and writes:
            live = np.flatnonzero(ref.live)
            rows = np.union1d(rng.choice(live, min(512, len(live)),
                                         replace=False),
                              np.intersect1d(np.asarray(extra_rows,
                                                        np.int64), live))
            comp.graph(ref, served, rows)
    return comp, ref


# -------------------------------------------------------------------- run
def prepare(c, seed: int, *, require_chip=True, build_fn=None):
    """Everything before the window: the deployment, served and warm.
    ``build_fn`` stands in for :func:`build` (the control, and the faults
    of the tests)."""
    import jax

    devices = jax.devices()
    dev = devices[0]
    if require_chip and (dev.platform != "tpu"
                         or len(devices) < c.cell["chips"]):
        log(f"chipbench: needs {c.cell['chips']} TPU chip(s); JAX sees "
            f"{len(devices)} {dev.platform} device(s)")
        raise SystemExit(2)
    if dev.platform == "tpu":
        use_compile_cache()
    from repro.serving import EngineConfig

    stalls = Stalls()
    counter = CompileCounter()
    engine_cfg = EngineConfig(**c.cfg["engine"])
    backend, ratings, spec, t_setup = (build_fn or build)(c, seed)
    t0 = time.monotonic()
    warm(backend, engine_cfg, c.mix, ratings.n_items)
    t_setup["warm"] = time.monotonic() - t0
    # what set-up made (JAX's own objects among them) is never garbage:
    # a full collection would walk it, ~0.1 s with the GIL held
    gc.collect()
    gc.freeze()
    return types.SimpleNamespace(
        dev=dev, devices=devices, used=devices[:c.cell["chips"]],
        counter=counter, stalls=stalls,
        engine_cfg=engine_cfg,
        backend=backend, ratings=ratings, spec=spec, t_setup=t_setup,
        landmarks=reflib.landmarks(ratings.counts(), spec.n_landmarks))


def drive(dep, c, seed: int, seconds: float, rate, trace: bool,
          trace_dir=None):
    """One window of the cell's traffic at ``rate``, drained."""
    from repro import obs as obslib
    from repro.serving import RequestEngine

    mix = c.mix
    open_loop = mix["loop"] == "open"
    rng = np.random.default_rng([seed, 2])
    n_writes = 0
    if open_loop:
        n_req = int(round(rate * seconds))
        n_writes = sum(int(round(m["share"] * n_req)) for m in mix["mix"]
                       if m["kind"] in trafficlib.WRITES)
        schedule = trafficlib.open_loop(mix, rate, seconds, seed,
                                        dep.ratings, dep.landmarks)
    else:
        order = trafficlib.closed_loop_order(mix, dep.ratings, seed)
    backend = dep.backend
    gen0 = backend.generation
    keep = {gen0} | {gen0 + int(g) for g in rng.choice(
        np.arange(1, n_writes + 1), min(KEEP_GENERATIONS, n_writes),
        replace=False)} if n_writes else {gen0}
    backend.reset(keep)
    obs = obslib.Observability(sample_rate=1.0, seed=seed) if trace else None
    engine = RequestEngine(backend, dep.engine_cfg, obs=obs)
    tracing = None
    if trace:
        trace_dir = Path(trace_dir or RUNS_DIR / c.name)
        shutil.rmtree(trace_dir, ignore_errors=True)
        tracing = Tracing(trace_dir)
    pauses = GcPauses()
    engine.start()
    compiles0 = dep.counter.count
    t_win = time.monotonic() + 0.05
    w = types.SimpleNamespace(setup_s=t_win - T_START, rng=rng, obs=obs,
                              late=None, trace=None)
    if tracing:
        tracing.start(t_win, seconds)
    if open_loop:
        handles, w.late = open_window(engine, schedule, t_win)
    else:
        closed = closed_window(engine, order, mix, t_win, seconds)
    t_close = t_win + seconds
    for h in ([h for _, _, h in handles] if open_loop
              else [h for _, _, h in closed]):
        if h is not None:
            h.done.wait(max(0.0, t_close + DRAIN_S - time.monotonic()))
    engine.stop()
    pauses.stop()
    w.compiles = dep.counter.count - compiles0
    if tracing:
        tracing.thread.join()
        from chipbench import trace_reduce

        path = trace_reduce.find_xplane(str(trace_dir))
        if path is None:
            log(f"trace: no capture ({tracing.error})")
        else:
            w.trace = trace_reduce.reduce(path)
            win = w.trace["window_s"]
            log(f"trace: window {win} s; device: busy s, idle % "
                + json.dumps({k: [v, 100.0 * (1.0 - v / win)] for k, v in
                              w.trace["device_busy_s"].items()}))
            log("trace: collective ops, s over all devices "
                + json.dumps({k: v for k, v in w.trace["ops"].items()
                              if any(c in k for c in COLLECTIVES)}))
            log(f"trace: {path}, {os.path.getsize(path)} bytes, modules "
                + json.dumps({k: [v, w.trace["module_runs"][k]]
                              for k, v in w.trace["modules"].items()}))
    w.stats = engine.stats()
    # the fullest of the cell's devices
    peaks = {d.id: (d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in dep.used}
    w.mem = max(peaks.values())

    # ---- what was served, and how late
    w.lat = {k: [] for k in trafficlib.READS + trafficlib.WRITES}
    w.reads, w.writes, w.written = [], [], []
    w.failed = 0
    w.users_done = 0
    next_user = dep.ratings.n_users
    if open_loop:
        w.attempted = len(handles)
        for r, due, h in handles:
            ok = h is not None and h.done.is_set()
            w.failed += not ok
            w.lat[r.kind].append((h.t_done - due) * 1e3 if ok else np.inf)
            if h is None:
                continue
            if r.kind in trafficlib.WRITES:
                user = next_user if r.kind == "fold" else int(r.users[0])
                next_user += r.kind == "fold"
                w.writes.append((r.kind, user,
                                 None if r.rows is None else r.rows[0]))
                w.written.append(user)
            elif ok and h.generation in backend.kept:
                w.reads.append({"kind": r.kind, "users": r.users,
                                "items": r.items, "result": h.result,
                                "gen": h.generation})
    else:
        w.attempted = len(closed)
        for users, _, h in closed:
            ok = h is not None and h.done.is_set()
            w.failed += not ok
            if ok and h.t_done <= t_close:
                w.users_done += len(users)
            if ok and h.generation in backend.kept:
                w.reads.append({"kind": "topn", "users": users,
                                "items": None, "result": h.result,
                                "gen": h.generation})
    if open_loop:
        log(f"generator: late p50 {np.median(w.late) * 1e3:.3f} ms, p99 "
            f"{quantile(w.late, 0.99) * 1e3:.3f} ms, max "
            f"{w.late.max() * 1e3:.3f} ms")
    st = w.stats
    log(f"window: {w.attempted} attempted, {w.failed} failed, shed "
        f"{st['shed']}, {w.compiles} compiles or traces inside, "
        f"{st['batches']} batches of {st['mean_batch_rows']:.1f} rows, "
        f"generation {backend.generation}, {len(backend.kept)} kept")
    log(f"device: peak_bytes_in_use {w.mem}, by device {json.dumps(peaks)}")
    log(f"gc: {len(pauses.gen2)} full collections in the window, longest "
        f"{max(pauses.gen2, default=0.0) * 1e3:.3f} ms")
    t_open = t_win - T_START
    dep.stalls.report(t_open, seconds)

    for kind, v in w.lat.items():
        if v:
            v = np.asarray(v)
            q = np.quantile(v[np.isfinite(v)], [0.5, 0.95, 0.99]) \
                if np.isfinite(v).any() else [np.inf] * 3
            log(f"latency {kind}: {len(v)} requests, p50 {q[0]:.3f} ms, "
                f"p95 {q[1]:.3f} ms, p99 {q[2]:.3f} ms, max "
                f"{v.max():.3f} ms")
    w.e2e = {}
    if w.lat["pair"]:
        w.e2e["pair_p95_ms"] = p95(w.lat["pair"])
    if w.lat["topn"]:
        w.e2e["topn_p95_ms"] = p95(w.lat["topn"])
    wl = w.lat["update"] + w.lat["fold"] + w.lat["remove"]
    if wl:
        w.e2e["write_p95_ms"] = p95(wl)
    if not open_loop:
        w.e2e["topn_users_per_s"] = w.users_done / seconds
    w.e2e["setup_s"] = w.setup_s
    for k, v in w.e2e.items():
        if not np.isfinite(v):
            w.e2e[k] = (seconds + DRAIN_S) * 1e3
    return w


def run(c, seed: int, seconds: float, trace: bool, *, require_chip=True,
        build_fn=None) -> dict:
    """One run of cell ``c``; returns the result object."""
    dep = prepare(c, seed, require_chip=require_chip, build_fn=build_fn)
    w = drive(dep, c, seed, seconds, c.load.get("rate_per_s"), trace)
    dep.stalls.stop()
    log("setup: " + ", ".join(f"{k} {v:.3f} s"
                              for k, v in dep.t_setup.items())
        + f", total {w.setup_s:.3f} s")
    backend = dep.backend
    ctx = types.SimpleNamespace(
        spans=w.obs.tracer.events() if w.obs else [], stats=w.stats,
        trace=w.trace, topn_batches=backend.topn_batches,
        served=None, nnz=None, topn=dep.engine_cfg.topn,
        device_kind=dep.dev.device_kind, log=log)
    if trace:
        from chipbench.served import state_arrays

        ctx.served = state_arrays(backend.view(backend.inner.snapshot()))

    # ---- correctness, on a sample of what was served, drawn from the seed:
    # requests in a seeded order, until each kind has its rows
    t0 = time.monotonic()
    sample = []
    for kind, most in c.cfg["check"].items():
        got = [r for r in w.reads if r["kind"] == kind]
        rows = 0
        for j in w.rng.permutation(len(got)):
            if rows >= most:
                break
            sample.append(got[j])
            rows += len(got[j]["users"])
    comp, ref = check(dep, sample, w.writes, w.rng, w.written)
    ctx.nnz = ref.counts()
    limits = c.cfg["limits"]
    log(f"reference: {time.monotonic() - t0:.1f} s, "
        + ", ".join(f"{k} over {comp.counts[k]}" for k in reflib.NUMBERS))
    for k in reflib.NUMBERS:
        if k not in limits:
            log(f"  not compared: {k} {comp.values[k]:.4e}")
    # an infinite reading (a bad id, a wrong row set) prints as 1e300, so
    # that the line stays strict JSON
    checks = {k: {"value": min(comp.values[k], 1e300), "limit": limits[k]}
              for k in reflib.NUMBERS if k in limits}
    # a request accepted and never answered is for ``correct``; one the
    # engine refused at admission only counts as failed
    unanswered = w.failed - sum(w.stats["shed"].values())
    correct = bool(all(v["value"] <= v["limit"] for v in checks.values())
                   and unanswered == 0)

    if trace:
        metrics = {}
        for m in c.per_layer:
            v = read_metric(m["name"], ctx)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    else:
        metrics = {m["name"]: {"value": w.e2e[m["name"]], "unit": m["unit"]}
                   for m in c.end_to_end if m["name"] in w.e2e}
    dev = dep.dev
    out = {"correct": correct, "attempted": w.attempted, "failed": w.failed,
           "metrics": metrics,
           "device": {"platform": dev.platform, "kind": dev.device_kind,
                      "count": len(dep.devices), "memory_peak_bytes": w.mem}}
    if trace and w.trace is not None:
        from chipbench import trace_reduce

        out["device"]["busy_s"] = w.trace["busy_s"]
        out["device"]["window_s"] = w.trace["window_s"]
        out["breakdown"] = trace_reduce.breakdown(w.trace)
        shutil.rmtree(RUNS_DIR / c.name, ignore_errors=True)
    out["checks"] = checks
    return out


def read_metric(name: str, ctx):
    path = BENCH / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(f"chipbench_metric_{name}",
                                                  path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read(ctx)


def report(out: dict) -> None:
    for k, v in out["checks"].items():
        log(f"check {k}: {v['value']:.4e} limit {v['limit']:.4e}")
    print(json.dumps(out, allow_nan=False), flush=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    c = load_cell(args.workload)
    report(run(c, args.seed, args.seconds, bool(args.trace)))
    return 0


if __name__ == "__main__":
    sys.exit(main())
