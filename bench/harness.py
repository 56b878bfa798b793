"""One run of one cell: set-up, the measured window, the check.

The process that holds the chip builds the engine from the cell's files
and serves it over HTTP through ``serving/server.py``; the client runs in
a spawned process (``bench/client.py``).  Set-up runs from process start to
the window's opening: the weights made on the device from the seed, the
engine, one pass of warm-up requests submitted straight to the engine
(which compiles, or loads from the checkout's compilation cache, every
program the cell's traffic uses: the unified step, the decode step and
the row reset for every number of admissions in one step), and the ramp
of the cell's own traffic.  The window then runs ``--seconds``, and its
drain ``DRAIN_S`` more, only to close out first tokens.

A cell on more than one chip serves over a ``(chips,)`` mesh whose axis
``tp`` the program shards heads and FFN over (``TP_SERVE_RULES``), its
weights made sharded.  What the harness reads of the model comes from the
configuration's family (``bench/arch/<model_type>.py``).

After the window the device's peak memory is read, the engine is freed,
and the family's reference (``bench/reference.py``) is run over a sample
of the requests the window finished, one layer at a time on one device;
``correct`` is true when every number compared is within its limit.
"""

from __future__ import annotations

import dataclasses
import gc
import importlib.util
import json
import math
import multiprocessing
import os
import shutil
import sys
import tempfile
import time
from pathlib import Path
from typing import Dict, List, Optional

import numpy as np

from bench import arch, client, gen, window, work

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
DRAIN_S = 5.0      # after the window: only to close out first tokens
TRACE_S = 10.0     # traced runs: the window's last seconds are profiled
LEAD_S = 1.0       # from handing the schedule to the client to the ramp
SAMPLE_TOKENS = 512  # served tokens the reference checks, at least ...
SAMPLE_MAX = 8       # ... in at most this many requests
WARM_PROMPT = 8


class NoChip(RuntimeError):
    """No TPU, or fewer chips than the cell asks for."""


def _json(path: Path) -> Dict:
    return json.loads(path.read_text())


@dataclasses.dataclass
class Cell:
    """Everything one cell's run reads, found by name."""
    name: str
    chips: int
    config: Dict        # bench/configs/<config>.json
    mix: Dict           # bench/traffic/<traffic>.json
    cell: Dict          # bench/cells/<name>.json
    end_to_end: List[Dict]
    per_layer: List[Dict]
    # bench/arch/<model_type>.py: everything the harness reads of the model
    family: object = dataclasses.field(init=False, repr=False)

    def __post_init__(self):
        self.family = arch.family(self.config.get("model_type"))

    @classmethod
    def load(cls, name: str, root: Path = ROOT) -> "Cell":
        spec = _json(root / "BENCHMARK.json")
        entry = [w for w in spec["workloads"] if w["name"] == name]
        if not entry:
            raise KeyError(f"no workload {name!r} in BENCHMARK.json")
        w = entry[0]
        conf = [c for c in spec["configs"] if c["name"] == w["config"]][0]

        def mine(m):
            return name in m.get("workloads", [name])

        return cls(name=name, chips=int(w["chips"]),
                   config=_json(root / conf["file"]),
                   mix=_json(root / "bench" / "traffic"
                             / f"{w['traffic']}.json"),
                   cell=_json(root / "bench" / "cells" / f"{name}.json"),
                   end_to_end=[m for m in spec["end_to_end"] if mine(m)],
                   per_layer=[m for m in spec["per_layer"] if mine(m)])


@dataclasses.dataclass
class Run:
    """What a per-layer metric's reader (``bench/metrics/<name>.py``) reads."""
    records: List[Dict]
    t0: float                 # window opens (perf_counter)
    t1: float                 # window closes
    deadline: float           # end of the drain
    probe: object             # bench.probe.Probe
    work: object              # the family's work(c): the whole cell's counts
    peak: Dict                # one chip's peaks
    compiles: int             # backend compilations inside the window
    chips: int = 1            # the cell's chips
    trace: Optional[Dict] = None   # trace_reduce.reduce() of the traced steps
    traced: List = dataclasses.field(default_factory=list)  # probe.Step


def _check_tree(cfg, weights) -> None:
    """The weights must be laid out as the program's parameter tree."""
    import jax

    from repro.models import model as model_lib

    want = model_lib.param_axes(cfg)[0]
    got = jax.tree.map(lambda a: (a.shape, str(a.dtype)), weights)
    exp = jax.tree.map(lambda a: (a.shape, str(a.dtype)), want)
    if got != exp:
        raise RuntimeError(f"weights do not match the program's tree: "
                           f"{got} != {exp}")


def _expected_tokens(prompt_len: int, max_new: int, max_len: int) -> int:
    """Tokens a request gets with no EOS: its budget, unless the engine's
    ``max_len`` stops it first (it stops when the next write position
    reaches ``max_len - 1``)."""
    return min(max_new, max(max_len - prompt_len, 1))


def _reader(name: str):
    spec = importlib.util.spec_from_file_location(
        f"bench_metric_{name.replace('.', '_')}",
        BENCH / "metrics" / f"{name}.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def _sample(records, plan, t0, deadline, seed) -> List[Dict]:
    """Requests the window finished (their last token arrived between its
    opening and the drain's end, whenever they were due): the longest
    (prompt plus output), then others in an order drawn from the seed,
    until ``SAMPLE_TOKENS`` served tokens or ``SAMPLE_MAX`` requests."""
    done = [r for r in records if r["done"] and r["arrivals"]
            and t0 <= r["arrivals"][-1][0] <= deadline]
    if not done:
        return []
    size = [len(plan[r["idx"]].prompt) + len(r["tokens"]) for r in done]
    first = int(np.argmax(size))
    rest = [done[i] for i in np.random.default_rng(seed % (1 << 63))
            .permutation(len(done)) if i != first]
    out, n = [done[first]], len(done[first]["tokens"])
    for r in rest:
        if n >= SAMPLE_TOKENS or len(out) >= SAMPLE_MAX:
            break
        out.append(r)
        n += len(r["tokens"])
    return out


def _ref_length(max_len: int) -> int:
    return int(math.ceil(max_len / 512) * 512)


def measure(c: Cell, seed: int, seconds: float, trace: bool, *,
            started: float, require_tpu: bool = True,
            control: bool = False) -> Dict:
    """One run; returns the result line's object (and a ``detail`` key,
    for the run's file, which the caller takes out).  ``control`` also
    reads the control's gap on the same sample (``bench/control.py``)."""
    ctx = multiprocessing.get_context("spawn")
    conn, child_conn = ctx.Pipe()
    proc = ctx.Process(target=client.serve_jobs, args=(child_conn,),
                       daemon=True)
    proc.start()
    try:
        return _measure(c, seed, seconds, trace, conn, started=started,
                        require_tpu=require_tpu, control=control)
    finally:
        try:
            conn.send(("stop",))
        except (BrokenPipeError, OSError):
            pass
        proc.join(timeout=30)
        if proc.is_alive():
            proc.kill()
            proc.join()


@dataclasses.dataclass
class Served:
    """A cell's engine, warmed up and behind its HTTP server."""
    engine: object
    probe: object
    handle: object
    params: Dict
    work: object
    devices: List
    peak: Dict
    compiles: object


def serve(c: Cell, seed: int, *, require_tpu: bool = True) -> Served:
    """Set-up up to the traffic: the weights from ``seed``, the engine
    (over a mesh of the cell's chips where it has more than one), its
    warm-up and its server.  Raises ``NoChip`` first where there is no TPU
    (unless ``require_tpu`` is off, for the CPU tests)."""
    import jax

    devices = jax.devices()
    if require_tpu and devices[0].platform != "tpu":
        raise NoChip(f"no TPU: JAX found {devices[0].platform!r}")
    if len(devices) < c.chips:
        raise NoChip(f"the cell needs {c.chips} chips, JAX found "
                     f"{len(devices)}")
    peak = work.peaks(devices[0].device_kind if require_tpu
                      else "TPU v5 lite")

    from bench import probe as probe_lib
    from repro.serving.engine import ServingEngine
    from repro.serving.sampling import SamplingParams
    from repro.serving.server import start_http_server

    compiles = probe_lib.Compiles()
    fam = c.family
    cfg = fam.program_config(c.config)
    counts = fam.work(c.config)
    mesh = axes = shardings = None
    if c.chips > 1:
        from repro.launch.mesh import make_mesh
        from repro.models import model as model_lib
        from repro.sharding import partition, rules

        mesh = make_mesh((c.chips,), ("tp",), devices=devices[:c.chips])
        shapes, axes = model_lib.param_axes(cfg)
        shardings = partition.param_shardings(axes, shapes, mesh,
                                              rules.TP_SERVE_RULES)
    params = fam.weights(c.config, seed, c.config["torch_dtype"], shardings)
    _check_tree(cfg, params)
    eng_kw = dict(c.cell["engine"])
    engine = ServingEngine(cfg, params, seed=seed % (1 << 31), mesh=mesh,
                           param_axes=axes, **eng_kw)
    probe = probe_lib.Probe(engine)

    # warm-up: every number of admissions in one step, from 1 to
    # max_batch, each through a unified step (prompt chunks); the first
    # also through a decode step
    rng = np.random.default_rng(0)
    for n in range(1, int(eng_kw["max_batch"]) + 1):
        for _ in range(n):
            engine.submit(rng.integers(0, counts.vocab, WARM_PROMPT,
                                       dtype=np.int32),
                          SamplingParams(max_new_tokens=2 if n == 1 else 1))
        engine.run()
    probe.reset()

    handle = start_http_server(engine)
    probe.wrap_hook()
    return Served(engine, probe, handle, params, counts, devices, peak,
                  compiles)


def offer(s: Served, c: Cell, conn, load: Dict, seed: int, seconds: float,
          trace_dir: Optional[str] = None):
    """The cell's traffic from ``seed`` through the client process: the
    ramp, the window and its drain.  Returns (plan, records, t0, t1,
    deadline), the window being ``[t0, t1)``."""
    plan = gen.schedule(c.mix, load, seed=seed, seconds=seconds,
                        drain=DRAIN_S, vocab=s.work.vocab)
    bodies = [client.request_body(p.prompt, p.max_new) for p in plan]
    ramp = float(load.get("ramp_s", 0.0))
    t0 = time.perf_counter() + LEAD_S + ramp
    t1 = t0 + seconds
    deadline = t1 + DRAIN_S
    if trace_dir is not None:
        s.probe.trace_between(t1 - min(TRACE_S, seconds), t1, trace_dir)
    conn.send(("open", s.handle.url,
               [(t0 + p.due, b, i) for i, (p, b) in
                enumerate(zip(plan, bodies))], deadline, deadline))
    return plan, conn.recv(), t0, t1, deadline


def _measure(c: Cell, seed: int, seconds: float, trace: bool, conn, *,
             started: float, require_tpu: bool, control: bool) -> Dict:
    import jax

    from bench import trace_reduce

    s = serve(c, seed, require_tpu=require_tpu)
    engine, probe, compiles, params = s.engine, s.probe, s.compiles, s.params
    devices, peak = s.devices, s.peak
    kind = devices[0].device_kind
    max_len = int(c.cell["engine"]["max_len"])
    load = c.cell["load"]
    tdir = tempfile.mkdtemp(prefix="bench-trace-") if trace else None
    plan, records, t0, t1, deadline = offer(s, c, conn, load, seed,
                                            seconds, tdir)
    mem = [d.memory_stats() or {} for d in jax.local_devices()[:c.chips]]
    mem_peak = max(int(m.get("peak_bytes_in_use", 0)) for m in mem)
    s.handle.close()
    probe.finish_trace()
    setup_s = t0 - started

    summary = window.summarize(records, t0, t1, deadline)
    late = window.lateness(records)
    device = {"platform": devices[0].platform, "kind": kind,
              "count": len(devices), "memory_peak_bytes": mem_peak}
    result: Dict = {"correct": False, "attempted": summary["attempted"],
                    "failed": summary["failed"]}
    detail = {"summary": summary, "lateness": late, "setup_s": setup_s,
              "window_compiles": compiles.between(t0, t1),
              "steps_in_window": len(probe.steps_in(t0, t1))}
    breakdown = None
    if trace:
        reduced = trace_reduce.reduce(trace_reduce.load(
            trace_reduce.find_xplane(tdir)))
        shutil.rmtree(tdir, ignore_errors=True)
        run = Run(records=records, t0=t0, t1=t1, deadline=deadline,
                  probe=probe, work=s.work, peak=peak,
                  compiles=compiles.between(t0, t1), chips=c.chips,
                  trace=reduced, traced=probe.traced)
        metrics = {}
        for m in c.per_layer:
            v = _reader(m["name"])(run)
            if v is not None:
                metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}
        del run
        device["busy_s"] = reduced["busy_s"]
        device["window_s"] = reduced["window_s"]
        breakdown = {"device_ops": reduced["device_ops"],
                     "idle_gaps": reduced["idle_gaps"]}
        detail["trace"] = {k: v for k, v in reduced.items() if k != "op_s"}
        detail["trace_op_s"] = reduced["op_s"]
    else:
        values = dict(summary, setup_s=setup_s)
        metrics = {m["name"]: {"value": float(values[m["name"]]),
                               "unit": m["unit"]}
                   for m in c.end_to_end if m["name"] in values}
    result["metrics"] = metrics
    result["device"] = device
    if breakdown is not None:
        result["breakdown"] = breakdown
    # marks a run whose client fell behind the cell's schedule
    result["schedule"] = {"late_sends": late["late_sends"],
                          "late_max_ms": late["late_max_ms"],
                          "limit_ms": window.LATE_S * 1e3}

    # the check, once the window is over and the engine is freed
    engine.cache = None
    del s, engine, probe
    gc.collect()
    t_ref = time.perf_counter()
    mismatch = sum(
        (len(r["tokens"]) != want) if r["done"] else (len(r["tokens"]) > want)
        for r in records
        for want in [_expected_tokens(len(plan[r["idx"]].prompt),
                                      plan[r["idx"]].max_new, max_len)])
    errors = sum(bool(r["error"]) for r in records)
    sample = [(plan[r["idx"]].prompt, r["tokens"])
              for r in _sample(records, plan, t0, deadline, seed)]
    fam = c.family
    gap = fam.worst_gap(params, c.config, sample,
                        _ref_length(max_len)) if sample else None
    limits = c.cell["limits"]
    checks = {
        "max_logit_gap": {"value": gap, "limit": limits["max_logit_gap"]},
        "length_mismatches": {"value": mismatch, "limit": 0},
        "request_errors": {"value": errors, "limit": 0},
    }
    result["correct"] = judge(checks)
    result["checks"] = checks
    detail.update(sample_requests=len(sample),
                  sample_tokens=sum(len(t) for _, t in sample),
                  reference_s=time.perf_counter() - t_ref)
    if control:
        # the control in the program's place: its tokens are the ones
        # served, its gap is read where the program's was, and the same
        # judgement decides
        ctl = fam.worst_gap(params, c.config, sample, _ref_length(max_len),
                            quant="fp8") if sample else None
        ctl_checks = dict(checks, max_logit_gap=dict(
            checks["max_logit_gap"], value=ctl))
        detail["control"] = {"correct": judge(ctl_checks),
                             "checks": ctl_checks}
    result["detail"] = detail
    return result


def judge(checks: Dict) -> bool:
    """``correct``: every number compared is there and within its limit."""
    return all(v["value"] is not None and v["value"] <= v["limit"]
               for v in checks.values())


def use_checkout_cache() -> None:
    """JAX's persistent compilation cache: ``JAX_COMPILATION_CACHE_DIR``
    where it is set, else ``.jax_cache`` in the checkout, at a fixed path
    (the path is part of each entry's key).  Every program is cached, the
    quick ones too, so that only a checkout's first run compiles."""
    import jax

    jax.config.update("jax_compilation_cache_dir",
                      os.environ.get("JAX_COMPILATION_CACHE_DIR")
                      or str(ROOT / ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def main(args, *, started: float) -> int:
    c = Cell.load(args.workload)
    use_checkout_cache()
    try:
        result = measure(c, args.seed, args.seconds, bool(args.trace),
                         started=started)
    except NoChip as e:
        print(f"bench: {e}", file=sys.stderr)
        return 3
    detail = result.pop("detail")
    out = Path(args.out)
    out.mkdir(parents=True, exist_ok=True)
    (out / f"{args.workload}.{args.seed}.trace{args.trace}.json").write_text(
        json.dumps({"result": result, "detail": detail}, indent=1))
    print("bench: " + json.dumps(detail), flush=True)
    late = result["schedule"]
    if late["late_sends"]:
        print(f"bench: off schedule: the client sent {late['late_sends']} "
              f"request(s) more than {late['limit_ms']:.0f} ms late, the "
              f"latest {late['late_max_ms']:.0f} ms; this run offered other "
              "load than the cell states", file=sys.stderr, flush=True)
    for name, v in result["checks"].items():
        print(f"check {name}: {v['value']} (limit {v['limit']})",
              file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
    return 0
