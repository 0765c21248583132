"""Configuration families: the architecture is a file that a configuration
brings (``bench/families/<model_type>.py``). The two dense families give
the weights and reference gaps pinned from the benchmark before the
families existed; an expert leaf is drawn at its fan-in; an unknown model
type names the file it wanted; and a family made of new files only, under
a model type of its own, runs a cell correct and has its work counted."""
import hashlib

import jax
import numpy as np
import pytest

from bench import families, harness, readers, run, weights
from bench.families import dense
from bench.reference import control_tokens, token_gaps
from bench.tests.test_bench_run import CELL, DATA, ROOT, fake_clock

SEED = 2**33 + 5
# Pinned from the benchmark as it was before the families, on the CPU. The
# weights are pinned to the bit. XLA's CPU dot sums in an order that depends
# on its thread count (one core and eight differ in the last bit), so the
# gaps are held to 1e-5, a few float32 steps of a logit: far below what any
# change to the forward moves them by.
PINNED = {
    "tiny-qwen3": dict(
        sha="cea8543b390cb3dfe3ac14cd70b4a372d2cb9e07dc8d3e8201549aed658bbe44",
        gaps=[3.3114562034606934, 3.544053077697754, 2.2862908840179443,
              3.668440818786621, 2.635225772857666, 2.7445616722106934,
              3.1220545768737793, 2.826941967010498, 1.8503570556640625,
              5.098877429962158, 2.137238025665283, 2.0823307037353516],
        control=[359, 2, 65, 101, 454, 214, 357, 416, 343, 96, 90, 198],
        control_gaps=[0.0, 0.11819911003112793, 0.0, 0.0, 0.0, 0.0,
                      0.06461048126220703, 0.147552490234375, 0.0, 0.0, 0.0,
                      0.08652806282043457]),
    "tiny-glm": dict(
        sha="9c23bed6a7b346e7ca763f94112be9c47e32eb44e0d967f761caf98b36b03559",
        gaps=[1.7879993915557861, 3.909888744354248, 3.0984771251678467,
              5.025266647338867, 2.7722244262695312, 3.06265926361084,
              2.0131354331970215, 1.847764253616333, 1.2287073135375977,
              3.923373222351074, 2.8724911212921143, 1.501347541809082],
        control=[350, 346, 293, 501, 196, 378, 483, 230, 42, 97, 38, 341],
        control_gaps=[0.0, 0.0, 0.0, 0.0, 0.03460693359375, 0.0, 0.0, 0.0,
                      0.0, 0.0, 0.0, 0.0]),
}


def tiny_weights(conf):
    cfg = harness.program_config(conf)
    return weights.make(harness.param_shapes(cfg), SEED,
                        families.of(conf).LEAVES)


@pytest.fixture(scope="module", params=sorted(PINNED))
def tiny(request):
    conf = harness.load_json(DATA + request.param + ".json")
    return request.param, conf, tiny_weights(conf)


def test_weights_match_the_pinned_checksum(tiny):
    name, _, params = tiny
    h = hashlib.sha256()
    for path, leaf in jax.tree_util.tree_flatten_with_path(params)[0]:
        h.update(jax.tree_util.keystr(path).encode())
        h.update(np.asarray(leaf).view(np.uint16).tobytes())
    assert h.hexdigest() == PINNED[name]["sha"]


def test_token_gaps_and_the_control_match_the_pinned_values(tiny):
    name, conf, params = tiny
    pin = PINNED[name]
    rng = np.random.default_rng(7)
    prompt = rng.integers(0, 512, 40).astype(np.int32)
    served = [int(x) for x in rng.integers(0, 512, 12)]
    np.testing.assert_allclose(
        token_gaps(params, conf, prompt, served),
        np.asarray(pin["gaps"], np.float32), atol=1e-5)
    ctl = control_tokens(params, conf, prompt, served)
    np.testing.assert_array_equal(ctl, pin["control"])
    np.testing.assert_allclose(
        token_gaps(params, conf, prompt, served, ctl),
        np.asarray(pin["control_gaps"], np.float32), atol=1e-5)


def test_a_stacked_expert_leaf_is_drawn_at_its_fan_in():
    # (layers, experts, in, out): the fan-in is 8, not the 4 experts
    shapes = {"stack": {"0": {"ffn": {"w_up": jax.ShapeDtypeStruct(
        (2, 4, 8, 3), np.float32)}}}}
    drawn = weights.make(shapes, 5, {}, dtype=np.float32)
    unit = weights.make(shapes, 5, {"w_up": "embedding"}, dtype=np.float32)
    w, z = (np.asarray(t["stack"]["0"]["ffn"]["w_up"]) for t in (drawn, unit))
    np.testing.assert_allclose(w, z / np.sqrt(np.float32(8)), rtol=1e-6)


def test_a_leaf_of_no_known_kind_fails():
    shapes = {"stack": {"0": {"mixer": {"wkv_a": jax.ShapeDtypeStruct(
        (2, 8, 3), np.float32)}}}}
    with pytest.raises(KeyError, match="stack/0/mixer/wkv_a"):
        weights.make(shapes, 5, {})


def test_an_unknown_model_type_fails_at_set_up_naming_the_file():
    conf = dict(harness.load_json(DATA + "tiny-qwen3.json"),
                model_type="no_such_family")
    want = str(ROOT / "bench" / "families" / "no_such_family.py")
    with pytest.raises(ValueError, match=want):
        run.run_cell(CELL, "tiny", 1, 0.5, False, require_chip=False,
                     conf=conf, mix=harness.load_json(DATA + "tiny-chat.json"),
                     **fake_clock())


def test_a_family_module_missing_part_of_the_contract_fails():
    with pytest.raises(ValueError, match="lacks arch"):
        families.of({"model_type": "dense"})


@pytest.fixture
def probe(monkeypatch):
    """The tests' data directory as the place families are found, holding
    the ``dense_probe`` family and its configuration ``tiny-probe.json``."""
    monkeypatch.setattr(families, "DIR", ROOT / "bench" / "tests" / "data")
    conf = harness.load_json(DATA + "tiny-probe.json")
    return conf, families.of(conf)


def test_a_family_brings_its_own_leaf_rule(probe):
    conf, fam = probe
    ln2 = np.asarray(tiny_weights(conf)["stack"]["0"]["ln2"], np.float32)
    # drawn as a bias, 0.1 z, where the default would draw a norm, 1 + 0.1 z
    assert abs(ln2.mean()) < 0.1 and ln2.std() < 0.2


def test_a_family_of_new_files_only_runs_correct_and_counts_its_work(
        probe, monkeypatch):
    conf, fam = probe
    seen = []

    def flops(ctx):
        seen.append(ctx)
        return float(sum(readers.work(ctx, d)[0] for d in ctx.dispatches))
    monkeypatch.setattr(run, "reader", lambda name: flops)
    cell = dict(CELL, per_layer=[{"name": "flops", "unit": "FLOP"}])
    out = run.run_cell(cell, "tiny", 2**32 + 13, 0.5, True,
                       require_chip=False, conf=conf,
                       mix=harness.load_json(DATA + "tiny-chat.json"),
                       **fake_clock())
    assert out["correct"] is True
    ctx, = seen
    assert ctx.family is fam and ctx.arch == fam.arch(conf)
    a, ds = ctx.arch, ctx.dispatches
    assert {d.kind for d in ds} == {"prefill", "decode"}
    want = sum(fam.decode_work(a, d.k, d.tokens // d.k, d.ctx)[0]
               if d.kind == "decode" else fam.prefill_work(a, d.tokens,
                                                           d.ctx)[0]
               for d in ds)
    assert out["metrics"]["flops"]["value"] == want
    assert want != sum(dense.decode_work(a, d.k, d.tokens // d.k, d.ctx)[0]
                       if d.kind == "decode" else
                       dense.prefill_work(a, d.tokens, d.ctx)[0] for d in ds)
