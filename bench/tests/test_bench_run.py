"""The harness end to end on the CPU at a tiny size: it refuses to measure
without a chip, and its check catches a wrong token."""
import json
import os
import pathlib
import shutil
import subprocess
import sys

import pytest

from bench import harness, run

ROOT = pathlib.Path(__file__).resolve().parents[2]
DATA = "bench/tests/data/"
CELL = {"workloads": [{"name": "tiny", "config": "x", "traffic": "x",
                       "chips": 1}], "end_to_end": [], "per_layer": []}


def bench_run(args, cwd):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run([sys.executable, "bench/run.py"] + args, cwd=cwd,
                          env=env, capture_output=True, text=True,
                          timeout=120)


def test_without_a_tpu_it_exits_nonzero_and_prints_no_result():
    p = bench_run(["--workload", "qwen3-32b.chat", "--seed", "1",
                   "--seconds", "1", "--trace", "0"], ROOT)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "TPU" in p.stderr


def test_with_only_the_benchmark_files_it_exits_nonzero(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = bench_run(["--workload", "qwen3-32b.chat", "--seed", "1",
                   "--seconds", "1", "--trace", "0"], tmp_path)
    assert p.returncode != 0 and p.stdout.strip() == ""


class FakeClock:
    """A millisecond passes per reading, and a sleep passes at once, so
    that what a tiny window serves does not hang on how busy the host is."""

    def __init__(self):
        self.t = 0.0

    def now(self):
        self.t += 1e-3
        return self.t

    def sleep(self, s):
        self.t += s


def fake_clock():
    c = FakeClock()
    return {"clock": c.now, "sleep": c.sleep}


def tiny(conf, mix, **kw):
    mixd = harness.load_json(DATA + mix)
    mixd["check"]["served_tokens"] = 48
    return run.run_cell(CELL, "tiny", kw.pop("seed", 3), 0.5, False,
                        require_chip=False, conf=harness.load_json(DATA + conf),
                        mix=mixd, **fake_clock(), **kw)


@pytest.fixture
def altered_decode(monkeypatch):
    """A fault where tokens are produced: slot 0's decoded token is off by
    one."""
    from repro.serving.scheduler import ContinuousBatchingScheduler as S
    orig = S._decode_multi

    def faulty(cfg, shard, *a, k):
        outs, cache = orig(cfg, shard, *a, k=k)
        return outs.at[:, 0].set((outs[:, 0] + 1) % cfg.vocab_size), cache
    monkeypatch.setattr(S, "_decode_multi", staticmethod(faulty))


@pytest.fixture
def altered_prefill(monkeypatch):
    """A fault where the first token is produced: the prefill program's
    argmax is off by one."""
    from repro.serving.scheduler import ContinuousBatchingScheduler as S
    orig = S._chunk_fn

    def faulty(self, n):
        fn = orig(self, n)
        return lambda *a: (lambda t, c: ((t + 1) % self.cfg.vocab_size, c))(
            *fn(*a))
    monkeypatch.setattr(S, "_chunk_fn", faulty)


@pytest.mark.parametrize("conf,mix", [("tiny-qwen3.json", "tiny-chat.json"),
                                      ("tiny-glm.json", "tiny-docs.json")])
def test_sound_run_is_correct_and_reports_every_metric(conf, mix):
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    cell = dict(CELL, end_to_end=[dict(m, workloads=["tiny"])
                                  for m in bench["end_to_end"]])
    out = run.run_cell(cell, "tiny", 2**32 + 11, 0.5, False,
                       require_chip=False, conf=harness.load_json(DATA + conf),
                       mix=harness.load_json(DATA + mix), **fake_clock())
    assert out["correct"] is True
    assert set(out["metrics"]) == {m["name"] for m in bench["end_to_end"]}
    assert list(out)[-1] == "checks"


def test_a_token_altered_in_decode_is_not_correct(altered_decode):
    assert tiny("tiny-qwen3.json", "tiny-chat.json")["correct"] is False


def test_a_token_altered_in_prefill_is_not_correct(altered_prefill):
    assert tiny("tiny-qwen3.json", "tiny-persona.json")["correct"] is False


def test_dispatch_log_waits_for_nothing_and_closes_at_the_next_dispatch():
    ticks = iter(range(100))
    log = harness.DispatchLog(lambda: float(next(ticks)))
    ran = []
    fn = lambda x, k=1: ran.append((x, k)) or x
    assert log.timed("prefill", fn, 7, tokens=5) == 7
    log.timed("decode", fn, 8, k=4, tokens=12, ctx_tokens=30)
    log.step_done()
    log.step_done()                       # a step with no dispatch
    log.timed("decode", fn, 9, k=1, tokens=3, ctx_tokens=40)
    log.step_done()
    assert ran == [(7, 1), (8, 4), (9, 1)]
    rows = [(d.kind, d.t0, d.t1, d.tokens, d.ctx, d.k) for d in log.rows]
    # each dispatch lasts until the next is issued or its step returns
    assert rows == [("prefill", 0.0, 1.0, 5, 0, 1),
                    ("decode", 1.0, 2.0, 12, 30, 4),
                    ("decode", 4.0, 5.0, 3, 40, 1)]
