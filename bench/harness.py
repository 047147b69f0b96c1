"""Run one cell of BENCHMARK.json once: set up, serve a measured window,
check what was served, print the result line.

Set-up builds the program's ``Server`` (``repro.launch.serve``), derives its
access plan, draws the weights from the seed, and warms up the two served
steps with the cell's shapes.  The window then drives ``Server.prefill`` and
``Server.decode`` one token at a time, as a streaming server would: each
step's greedy tokens are read back to the host, the next step already
dispatched.  A closed loop: the next batch is due when the last token of the
one before reaches the host.  After the window the served state is freed and
the reference (``bench/reference/<family>.py``) recomputes a seeded sample of
the finished requests.

Nothing here is specific to one cell or one model family: a cell is its
configuration, traffic and cell files, a family is its module under
``bench/reference/``, and a metric is its reader under ``bench/metrics/``.
The Pallas kernels told apart in a trace are those the cell's readers
declare (``KERNEL``).
"""

from __future__ import annotations

import gc
import importlib.util
import json
import os
import sys
import tempfile
import threading
import time
from dataclasses import dataclass, field
from pathlib import Path

import numpy as np

from bench import traffic
from bench.reference import family

ROOT = Path(__file__).resolve().parents[1]
BENCH = ROOT / "bench"
TRACE_SECONDS = 3.0  # the traced part of a --trace 1 window


class NoDevice(Exception):
    """JAX found no accelerator, or fewer chips than the cell asks for."""


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


# -- the cell, from its files ------------------------------------------------


@dataclass
class Cell:
    name: str
    chips: int
    config: dict  # the configuration file
    mix: traffic.Mix
    check: dict  # the cell file: sample size and limits
    end_to_end: list  # the metric entries this cell reports, by mode
    per_layer: list
    root: Path = ROOT  # the benchmark's root: its files and metric readers

    @property
    def model(self) -> dict:
        return model_block(self.config)


def model_block(config: dict) -> dict:
    """A configuration's ``model`` block with its family's name
    (``reference``), as the family's module, ``weights`` and ``flops`` take
    it."""
    return {**config["model"], "reference": config["reference"]}


def _reports(metric: dict, workload: str) -> bool:
    cells = metric.get("workloads")
    return workload in cells if cells is not None else True


def load_cell(workload: str, root: Path = ROOT) -> Cell:
    manifest = json.loads((root / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in manifest["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r} in BENCHMARK.json")
    w = cells[workload]
    conf = next(c for c in manifest["configs"] if c["name"] == w["config"])
    config = json.loads((root / conf["file"]).read_text())
    check_published(config)
    mix = traffic.load(root / "bench" / "traffic" / f"{w['traffic']}.json")
    check = json.loads((root / "bench" / "cells" / f"{workload}.json").read_text())
    e2e = [m for m in manifest["end_to_end"] if _reports(m, workload)]
    reported = {m["name"] for m in e2e}
    layer = [m for m in manifest["per_layer"]
             if _reports(m, workload) and m["moves"] in reported]
    return Cell(workload, int(w["chips"]), config, mix, check, e2e, layer, root)


def check_published(config: dict) -> None:
    """Each size of the ``model`` block equals the published key it comes
    from (``model_from``), as the file states it."""
    m = config["model"]
    for key, pub in config["model_from"].items():
        if m[key] != config[pub]:
            raise ValueError(f"model.{key} = {m[key]!r} but {pub} = {config[pub]!r}")


def program_config(config: dict):
    """The program's own configuration of this model, checked against the
    file: each field that the family's ``program_fields`` names."""
    from repro.configs import get_config, get_smoke_config

    name = config["program_config"]
    cfg = (get_smoke_config if config.get("program_preset") == "smoke" else get_config)(name)
    m = model_block(config)
    want = family(m).program_fields(m)
    bad = {k: (getattr(cfg, k), v) for k, v in want.items() if getattr(cfg, k) != v}
    if bad:
        raise ValueError(f"repro.configs {name} differs from the file: {bad}")
    return cfg


# -- the device ---------------------------------------------------------------


def devices(chips: int):
    import jax

    devs = jax.devices()
    if devs[0].platform == "cpu" or len(devs) < chips:
        raise NoDevice(f"JAX found {len(devs)} {devs[0].platform} device(s); "
                       f"the cell needs {chips} accelerator chip(s)")
    return devs[:chips]


def peaks(kind: str) -> dict:
    table = json.loads((BENCH / "peaks.json").read_text())["devices"]
    if kind not in table:
        raise KeyError(f"no peaks for device kind {kind!r} in bench/peaks.json")
    return table[kind]


def memory_peak(devs) -> int:
    return max((d.memory_stats() or {}).get("peak_bytes_in_use", 0) for d in devs)


def setup_compile_cache() -> str:
    """The program's own compile cache (``repro.launch.compile_cache``: a
    fixed path in the checkout unless ``JAX_COMPILATION_CACHE_DIR`` names
    one), with every program kept however quickly it compiled, so that a
    second run compiles nothing."""
    import jax

    from repro.launch.compile_cache import setup_compile_cache as program_cache

    path = program_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return str(path)


class CompileCounter:
    """Counts the programs JAX traces or compiles, to show that none does
    inside the window."""

    EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
              "/jax/core/compile/backend_compile_duration")

    def __init__(self):
        import jax.monitoring

        self.n = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, _secs: float, **_kw) -> None:
        if event in self.EVENTS:
            self.n += 1


class GcPauses:
    """The longest pause of Python's garbage collector while it is open."""

    def __init__(self):
        self.longest, self._t = 0.0, None
        gc.callbacks.append(self._on)

    def _on(self, phase: str, _info: dict) -> None:
        if phase == "start":
            self._t = time.perf_counter()
        elif self._t is not None:
            self.longest = max(self.longest, time.perf_counter() - self._t)

    def close(self) -> float:
        gc.callbacks.remove(self._on)
        return self.longest


class Heartbeat:
    """A thread that wakes every 10 ms and keeps its longest gap between two
    wakes.  Where a wait for a token is long and this gap is not, the
    process ran on while the runtime held the token; where both are long,
    the whole process stood still."""

    def __init__(self):
        self.longest, self.at = 0.0, 0.0
        self._stop = threading.Event()
        self._thread = threading.Thread(target=self._run, name="bench.heartbeat", daemon=True)
        self._thread.start()

    def _run(self) -> None:
        last = time.perf_counter()
        while not self._stop.wait(0.01):
            now = time.perf_counter()
            if now - last > self.longest:
                self.longest, self.at = now - last, now
            last = now

    def close(self) -> tuple:
        """(longest gap, host time it ended)."""
        self._stop.set()
        self._thread.join()
        return self.longest, self.at


# -- serving -------------------------------------------------------------------


@dataclass
class Batch:
    index: int
    due: float  # host clock: when the batch was submitted
    times: list = field(default_factory=list)  # host clock of each token step
    tokens: list = field(default_factory=list)  # [batch] ids of each step


@dataclass
class Window:
    start: float
    seconds: float
    batches: list
    decode_kv_lens: list  # kv_len of the decode steps inside the trace
    trace: object = None
    compiles: int = 0
    gc_pause: float = 0.0
    heartbeat: tuple = (0.0, 0.0)  # Heartbeat.close()

    @property
    def end(self) -> float:
        return self.start + self.seconds

    def longest_gaps(self, n: int = 3) -> list:
        """(seconds, host time it ended) of the longest waits between two
        tokens reaching the host, across batches too."""
        times = sorted(t for b in self.batches for t in b.times if t <= self.end)
        gaps = [(b - a, b) for a, b in zip([self.start] + times, times)]
        return sorted(gaps, reverse=True)[:n]


class Served:
    """The system under test, set up for one cell and one seed."""

    def __init__(self, cell: Cell, seed: int, server=None):
        """``server``: one already built for this cell (the control's
        seeds share it); otherwise one is built and its plan derived."""
        import jax
        import jax.numpy as jnp

        from bench import weights
        from repro.launch.serve import Server

        self.cell, self.seed = cell, seed
        mix, m = cell.mix, cell.model
        self.plan_derive_s = None
        if server is None:
            server = Server(program_config(cell.config), max_len=mix.prompt_len + mix.output_len)
            t0 = time.perf_counter()
            plan = server.plan(mix.batch)
            self.plan_derive_s = time.perf_counter() - t0
            log(f"access plan: {len(plan.records)} records, {plan.total_bytes} bytes per "
                f"decode step, derived in {self.plan_derive_s:.6f} s")
        self.server = server
        abstract = self.server.model.abstract_params()
        self.vocab_rows = abstract["embed"].shape[0]
        self.key = weights.root_key(seed)
        t0 = time.perf_counter()
        self.params = weights.make_params(m, self.key, self.vocab_rows, m["dtype"])
        jax.block_until_ready(self.params)
        t1 = time.perf_counter()
        got = jax.tree.map(lambda x: (x.shape, x.dtype), self.params)
        want = jax.tree.map(lambda x: (x.shape, x.dtype), abstract)
        if got != want:
            raise ValueError(f"drawn weights do not match the program's tree:\n{got}\n{want}")
        self.argmax = jax.jit(lambda logits: jnp.argmax(logits, axis=-1).astype(jnp.int32))
        self._warm_up()
        log(f"set-up: weights drawn in {t1 - t0:.6f} s, warm-up {time.perf_counter() - t1:.6f} s")

    def _warm_up(self) -> None:
        """Compile, or load from the cache, every program the window runs:
        the cell's prefill and decode shapes and the greedy pick."""
        import jax

        mix = self.cell.mix
        prompts = jax.device_put(np.zeros((mix.batch, mix.prompt_len), np.int32))
        logits, cache = self.server.prefill(self.params, {"inputs": prompts})
        tok = self.argmax(logits)
        for i in range(min(2, mix.output_len - 1)):
            logits, cache = self.server.decode(self.params, cache, tok, mix.prompt_len + i)
            tok = self.argmax(logits)
        np.asarray(tok)

    def serve(self, seconds: float, trace_dir: str | None = None,
              finish: int | None = None, fault=None) -> Window:
        """The measured window: ``seconds`` long, or until ``finish``
        requests have finished where that is given (the control's short
        window).  ``fault`` (tests only) breaks the served path."""
        import jax

        mix, V = self.cell.mix, self.cell.model["vocab_size"]
        P, O = mix.prompt_len, mix.output_len
        prefill, decode, argmax = self.server.prefill, self.server.decode, self.argmax
        if fault is not None:
            prefill, decode, argmax = fault(prefill, decode, argmax)
        span = jax.profiler.TraceAnnotation
        counter, pauses, heart = CompileCounter(), GcPauses(), Heartbeat()
        kv_lens, batches = [], []
        tracing = trace_dir is not None
        if tracing:
            jax.profiler.start_trace(trace_dir)
            traced = span("bench.traced")
            traced.__enter__()
        nxt_prompts = jax.device_put(mix.prompts(self.seed, 0, V))
        start = due = time.perf_counter()
        end, trace_end = start + seconds, start + min(TRACE_SECONDS, seconds)
        compiles0, n, t, nxt = counter.n, 0, start, None
        while t <= end:
            batch = Batch(n, due)
            batches.append(batch)
            with span("bench.dispatch"):
                logits, cache = prefill(self.params, {"inputs": nxt_prompts})
                tok = argmax(logits)
            with span("bench.prepare"):
                nxt_prompts = jax.device_put(mix.prompts(self.seed, n + 1, V))
            for i in range(O):
                if i < O - 1:
                    with span("bench.dispatch"):
                        logits, cache = decode(self.params, cache, tok, P + i)
                        nxt = argmax(logits)
                    if tracing:
                        kv_lens.append(P + i + 1)
                with span("bench.readback"):
                    host = np.asarray(tok)[:, 0]
                t = time.perf_counter()
                batch.times.append(t)
                batch.tokens.append(host)
                if tracing and t > trace_end:
                    traced.__exit__(None, None, None)
                    jax.profiler.stop_trace()
                    tracing = False
                if t > end:
                    break
                if i < O - 1:
                    tok = nxt
            jax.block_until_ready((logits, cache, argmax(logits)))
            del logits, cache, tok, nxt
            due, n = t, n + 1
            if finish is not None and sum(len(b.times) == O for b in batches) * mix.batch >= finish:
                break
        if tracing:
            traced.__exit__(None, None, None)
            jax.profiler.stop_trace()
        compiles = counter.n - compiles0
        return Window(start, seconds if finish is None else t - start, batches, kv_lens,
                      compiles=compiles, gc_pause=pauses.close(), heartbeat=heart.close())

    def free(self) -> None:
        """Drop the served weights, so the reference has the chip's memory."""
        self.params = None


# -- checking what was served ---------------------------------------------------


def finished(window: Window, mix: traffic.Mix) -> list:
    """(batch, row) of every request whose last token reached the host
    inside the window."""
    return [(b.index, r) for b in window.batches
            if len(b.times) == mix.output_len and b.times[-1] <= window.end
            for r in range(mix.batch)]


def sample(window: Window, cell: Cell, seed: int) -> list:
    """The requests to check, drawn from the seed among the finished ones
    (all of a mix's requests are equally long, so any is a longest).  The
    picks cover the batch's rows evenly: with k picks of a batch of B, pick
    j takes a row in the j-th of k equal runs of rows (k <= B), or row
    j mod B (k > B), so that a fault in any part of the batch is seen."""
    done = finished(window, cell.mix)
    k = min(int(cell.check["sample_requests"]), len(done))
    B = cell.mix.batch
    batches = sorted({n for n, _ in done})
    rng = np.random.default_rng([seed, 2])
    picks = set()
    for j in range(k):
        rows = range(j * B // k, max((j + 1) * B // k, j * B // k + 1)) if k <= B else [j % B]
        free = [(n, r) for n in batches for r in rows if (n, r) not in picks]
        picks.add(free[rng.integers(len(free))])
    return sorted(picks)


def gaps(cell: Cell, key, vocab_rows: int, seed: int, window: Window, picks: list,
         control: bool = False) -> dict:
    """For each sampled request, the reference's logits at each served
    position, teacher-forced on the served tokens.  ``logit_gap``: the
    widest gap by which a served token's logit lies below the reference's
    best.  With ``control`` the reference computed in float8 is put in the
    program's place: its pick at each of those positions is compared
    instead of the served token (``served_gap`` keeps the program's)."""
    import jax.numpy as jnp

    mix, m = cell.mix, cell.model
    ref = family(m)
    P, O = mix.prompt_len, mix.output_len
    by_index = {b.index: b for b in window.batches}
    rows, served = [], []
    for n, r in picks:
        out = np.array([step[r] for step in by_index[n].tokens], np.int32)
        rows.append(np.concatenate([mix.prompts(seed, n, m["vocab_size"])[r], out[:-1]]))
        served.append(out)
    tokens, served = np.stack(rows), jnp.asarray(np.stack(served))
    block = int(cell.check.get("reference_rows", 1))
    pad = -len(tokens) % block
    tokens = np.concatenate([tokens, tokens[:pad]]) if pad else tokens
    want = ref.logits(m, key, vocab_rows, tokens, P - 1, block=block)[: len(picks)]
    best = want.max(axis=-1)

    def widest(picked):
        got = jnp.take_along_axis(want, picked[..., None], axis=-1)[..., 0]
        return float((best - got).max()), float((want.argmax(-1) == picked).mean())

    out = {}
    if control:
        low = ref.logits(m, key, vocab_rows, tokens, P - 1, quant="fp8", block=block)
        out["served_gap"] = widest(served)[0]
        served = low[: len(picks)].argmax(-1)
    out["logit_gap"], out["agree"] = widest(served)
    return out


# -- one run ---------------------------------------------------------------------


@dataclass
class Run:
    """What a metric reader reads."""
    cell: Cell
    window: Window
    setup_s: float
    plan_derive_s: float
    peaks: dict
    trace: object = None


def reader(name: str, root: Path = ROOT):
    """The metric's reader, ``bench/metrics/<name>.py`` under the root."""
    path = root / "bench" / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(f"bench_metric_{name.replace('.', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def read_metric(name: str, run: Run):
    return reader(name, run.cell.root).read(run)


def kernels(cell: Cell) -> tuple:
    """The Pallas kernels to tell apart in the cell's trace: the ``KERNEL``
    that each of its per-layer readers declares."""
    declared = (getattr(reader(m["name"], cell.root), "KERNEL", None) for m in cell.per_layer)
    return tuple(sorted({k for k in declared if k}))


def load_trace(xplane: Path, cell: Cell):
    """The cell's trace with each op's scope (``scope_trace.load``), its
    readers' kernels told apart."""
    from bench import scope_trace

    return scope_trace.load(xplane, kernels(cell))


def run_cell(workload: str, seed: int, seconds: float, trace: bool, t_start: float,
             root: Path = ROOT, fault=None, need_chip: bool = True, control: bool = False,
             finish: int | None = None) -> dict:
    """One run; returns the result line's object.  ``fault`` breaks the
    served path (tests); ``control`` puts the float8 reference's tokens in
    the place of the served ones before they are compared; ``finish`` ends
    the window once that many requests have finished (the control's
    short window)."""
    cell = load_cell(workload, root)
    import jax

    cache_dir = setup_compile_cache()
    if need_chip:
        devs = devices(cell.chips)
    else:
        devs = jax.devices()[: cell.chips]
    dev = devs[0]
    log(f"device: platform={dev.platform} kind={dev.device_kind} count={len(devs)} "
        f"jax={jax.__version__} compile cache {cache_dir}; found at "
        f"+{time.perf_counter() - t_start:.6f} s")
    pk = peaks(dev.device_kind) if need_chip else {"bf16_flops": 1.0, "hbm_bytes_per_s": 1.0}
    served = Served(cell, seed)
    setup_s = time.perf_counter() - t_start
    log(f"set-up: {setup_s:.6f} s")
    tmp = tempfile.mkdtemp(prefix="bench_trace_") if trace else None
    window = served.serve(seconds, trace_dir=tmp, finish=finish, fault=fault)
    peak = memory_peak(devs)
    log(f"window: {len(window.batches)} batches, {window.compiles} programs traced or "
        f"compiled inside it; memory_peak_bytes {peak}")
    waits = ", ".join(f"{g:.6f} s ending at +{t - t_start:.3f} s" for g, t in window.longest_gaps())
    beat, beat_at = window.heartbeat
    log(f"host: longest waits for a token {waits}; longest GC pause {window.gc_pause:.6f} s; "
        f"longest heartbeat gap {beat:.6f} s ending at +{beat_at - t_start:.3f} s")
    key, vocab_rows = served.key, served.vocab_rows
    plan_derive_s = served.plan_derive_s
    served.free()
    del served
    if tmp is not None:
        window.trace = load_trace(next(Path(tmp).rglob("*.xplane.pb")), cell)
        _rmtree(tmp)
    picks = sample(window, cell, seed)
    t0 = time.perf_counter()
    result = gaps(cell, key, vocab_rows, seed, window, picks, control) if picks else {}
    log(f"reference over {len(picks)} requests: {time.perf_counter() - t0:.3f} s, "
        f"compared token = reference's best at {result.get('agree', 0):.6f} of positions"
        + (f"; control in the program's place (served tokens' gap {result.get('served_gap')})"
           if control else ""))
    limits = cell.check["limits"]
    checks = {name: {"value": result.get(name), "limit": limit} for name, limit in limits.items()}
    correct = bool(picks) and all(
        c["value"] is not None and c["limit"] is not None and c["value"] <= c["limit"]
        for c in checks.values())
    run = Run(cell, window, setup_s, plan_derive_s, pk, window.trace)
    metrics = {}
    for m in (cell.per_layer if trace else cell.end_to_end):
        value = read_metric(m["name"], run)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    attempted = sum(len(b.tokens) > 0 for b in window.batches) * cell.mix.batch
    device = {"platform": dev.platform, "kind": dev.device_kind, "count": len(devs),
              "memory_peak_bytes": peak}
    out = {"correct": correct, "attempted": attempted, "failed": 0, "metrics": metrics,
           "device": device}
    if trace and window.trace is not None:
        t = window.trace
        device["busy_s"] = t.mean_busy_ns() * 1e-9
        device["window_s"] = t.window_ns * 1e-9
        out["breakdown"] = {"device_ops": t.top_ops(10), "idle_gaps": t.idle_gaps(10)}
    for name, c in checks.items():
        log(f"check {name}: {c['value']} limit {c['limit']}")
    out["checks"] = checks
    return out


def _rmtree(path: str) -> None:
    import shutil

    shutil.rmtree(path, ignore_errors=True)
