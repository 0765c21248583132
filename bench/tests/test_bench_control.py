"""The control: the fp8 forward's tokens in the program's place come out not
correct through the harness's own check, on the same requests whose
bfloat16 tokens pass it (tiny size, CPU)."""
import pytest

from bench import harness, run
from bench.tests.test_bench_run import fake_clock

DATA = "bench/tests/data/"
CELL = {"workloads": [{"name": "tiny", "config": "x", "traffic": "x",
                       "chips": 1}], "end_to_end": [], "per_layer": []}


@pytest.mark.parametrize("seed", [1, 2, 3])
@pytest.mark.parametrize("conf,mix", [("tiny-qwen3.json", "tiny-chat.json"),
                                      ("tiny-glm.json", "tiny-docs.json"),
                                      ("tiny-qwen3.json",
                                       "tiny-persona.json")])
def test_control_fails_where_the_program_passes(conf, mix, seed):
    mixd = harness.load_json(DATA + mix)
    mixd["check"]["served_tokens"] = 96
    out = run.run_cell(CELL, "tiny", seed, 1.0, False, require_chip=False,
                       conf=harness.load_json(DATA + conf), mix=mixd,
                       control=True, **fake_clock())
    assert out["correct"] is False
    assert out["checks"]["gap_max"]["value"] > out["checks"]["gap_max"][
        "limit"]
    assert out["program"]["correct"] is True
