"""Traffic generation: trace determinism, replay mode, open-loop driving,
and benchmark-harness key validation."""

import jax
import numpy as np
import pytest

from repro.configs import get_config
from repro.models import model as model_lib
from repro.serving.engine import ServingEngine
from repro.serving.workload import (LengthDist, OpenLoopDriver, WorkloadSpec,
                                    poisson_trace, replay_trace)


def _traces_equal(a, b):
    return (len(a) == len(b)
            and all(x.time_s == y.time_s
                    and np.array_equal(x.prompt, y.prompt)
                    and x.params == y.params for x, y in zip(a, b)))


def test_poisson_trace_deterministic_per_seed():
    spec = WorkloadSpec(arrival_rate=4.0, num_requests=16, seed=7)
    t1, t2 = poisson_trace(spec, 256), poisson_trace(spec, 256)
    assert _traces_equal(t1, t2)
    t3 = poisson_trace(WorkloadSpec(arrival_rate=4.0, num_requests=16, seed=8), 256)
    assert not _traces_equal(t1, t3)
    # arrival times are non-decreasing and roughly rate-scaled
    times = [a.time_s for a in t1]
    assert times == sorted(times)
    assert 0.5 < times[-1] < 30.0


def test_length_dists():
    rng = np.random.default_rng(0)
    assert LengthDist(kind="fixed", mean=12).sample(rng) == 12
    u = [LengthDist(kind="uniform", low=3, high=9).sample(rng) for _ in range(50)]
    assert all(3 <= n <= 9 for n in u)
    ln = [LengthDist(kind="lognormal", mean=32, low=1, high=512).sample(rng)
          for _ in range(200)]
    assert 16 < np.mean(ln) < 64
    with pytest.raises(ValueError):
        LengthDist(kind="zipf").sample(rng)


def test_replay_trace_deterministic():
    sched = [(0.0, 5, 4), (0.1, 9, 6), (0.25, 7, 2)]
    a, b = replay_trace(sched, 256), replay_trace(sched, 256)
    assert _traces_equal(a, b)
    assert [x.time_s for x in a] == [0.0, 0.1, 0.25]
    assert [len(x.prompt) for x in a] == [5, 9, 7]
    assert [x.params.max_new_tokens for x in a] == [4, 6, 2]


def test_open_loop_driver_serves_trace():
    import time

    cfg = get_config("qwen1.5-0.5b", smoke=True)
    params, _ = model_lib.init(cfg, jax.random.PRNGKey(0))
    eng = ServingEngine(cfg, params, max_batch=2, max_len=64, prompt_bucket=8)
    schedule = [(0.0, 5, 3), (0.3, 8, 4), (0.6, 6, 3)]
    arrivals = replay_trace(schedule, cfg.vocab_size)
    t0 = time.perf_counter()
    finished = OpenLoopDriver(eng, arrivals).run()
    assert sorted(len(r.output_tokens) for r in finished) == [3, 3, 4]
    # open-loop: request i (uid == submission order) cannot have been
    # submitted before its scheduled arrival time
    for r in finished:
        assert r.submit_time - t0 >= schedule[r.uid][0] - 1e-6


def test_benchmark_run_rejects_unknown_keys(capsys):
    from benchmarks import run as bench_run

    with pytest.raises(SystemExit) as e:
        bench_run.main(["--only", "tabel2,nope"])
    assert e.value.code == 2
    err = capsys.readouterr().err
    assert "unknown module key" in err and "table2" in err


def test_benchmark_run_records_a_failing_module_and_exits_nonzero(
        monkeypatch, tmp_path, capsys):
    from benchmarks import run as bench_run

    def broken(csv_rows):
        raise RuntimeError("boom")

    monkeypatch.setitem(bench_run.MODULES, "table2",
                        type("Broken", (), {"run": staticmethod(broken)}))
    # RESULTS.md is written beside the module: keep the checked-in one
    monkeypatch.setattr(bench_run, "__file__", str(tmp_path / "run.py"))
    assert bench_run.main(["--only", "table2"]) == 1
    assert "table2: FAILED" in (tmp_path / "RESULTS.md").read_text()
    assert "table2,0,FAILED" in capsys.readouterr().out
