"""The harness finds a cell's pieces by name: a new traffic mix and a
new per-layer metric are new files plus new BENCHMARK.json entries,
with no edit to a file that is already there."""
import json
import os
import shutil

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import spec, traffic, weights
from bench.flops import Shapes

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture
def checkout(tmp_path):
    """A copy of the benchmark's files, then a dummy mix, a dummy metric
    and a cell that uses both, added as new files and entries only."""
    root = tmp_path / "checkout"
    shutil.copytree(os.path.join(REPO, "bench"), root / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(os.path.join(REPO, "BENCHMARK.json"), root)
    before = {p: open(p, "rb").read() for p in _files(root)}
    mix = {"arrival": {"kind": "backlog", "requests": 3}, "ramp_s": 0.0,
           "shared_prefix": {"tokens": 0, "count": 0},
           "prompt": {"median": 64, "sigma": 0.1, "min": 16, "max": 128},
           "output": {"median": 8, "sigma": 0.1, "min": 4, "max": 16},
           "round_to": 16, "schedule_seed": 1}
    (root / "bench" / "traffic" / "dummy.json").write_text(json.dumps(mix))
    (root / "bench" / "metrics" / "dummy.tokens_seen.py").write_text(
        "def read(run):\n    return float(run.tokens)\n")
    b = json.loads((root / "BENCHMARK.json").read_text())
    b["workloads"].append({"name": "slm.dummy", "config": "stablelm-3b",
                           "traffic": "dummy", "chips": 1,
                           "why": "test only"})
    b["per_layer"].append({"name": "dummy.tokens_seen", "unit": "tokens",
                           "better": "higher", "source": "program_counter",
                           "layer": "scheduler", "moves": "output_tok_s",
                           "workloads": ["slm.dummy"]})
    (root / "BENCHMARK.json").write_text(json.dumps(b))
    yield root
    # nothing that was there has changed
    for p, data in before.items():
        if not p.endswith("BENCHMARK.json"):
            assert open(p, "rb").read() == data, p


def _files(root):
    return [os.path.join(d, f) for d, _, fs in os.walk(root) for f in fs
            if "__pycache__" not in d]


def test_new_mix_and_metric_are_found_by_name(checkout):
    cell = spec.load_cell("slm.dummy", root=str(checkout))
    assert cell.traffic["arrival"] == {"kind": "backlog", "requests": 3}
    assert cell.config["registry"] == "stablelm-3b"
    reqs = traffic.generate(cell.traffic, 1000, 1, 5.0)
    assert len(reqs) == 3 and all(r.due_s == 0.0 for r in reqs)
    names = [m.name for m in cell.per_layer]
    assert "dummy.tokens_seen" in names
    assert "kv.preempted_per_req" not in names    # listed for longdoc

    class Run:
        tokens = 42
    got = spec.read_metrics([m for m in cell.per_layer
                             if m.name == "dummy.tokens_seen"], Run(),
                            str(checkout / "bench"))
    assert got == {"dummy.tokens_seen": {"value": 42.0, "unit": "tokens"}}


def test_cells_report_only_their_metrics():
    doc = spec.load_cell("stablelm3b.longdoc")
    assert [m.name for m in doc.end_to_end] == [
        "tpot_p90_ms", "output_tok_s", "setup_s"]
    assert "kv.preempted_per_req" in [m.name for m in doc.per_layer]
    for m in doc.per_layer:
        assert callable(spec.reader(m.name))


def test_a_reader_that_finds_nothing_is_left_out():
    class Empty:
        device_trace = None
        ticks = []
        events = []
    ms = [spec.Metric(n, "%") for n in
          ("step.decode_mfu", "kernel.paged_attn_roofline",
           "device.idle_share", "step.decode_ms")]
    assert spec.read_metrics(ms, Empty()) == {}


def test_stacked_weights_equal_the_reference_draws_bit_for_bit():
    """The program's tree (one jitted call) and the reference's
    layer-by-layer draws hold the same numbers."""
    s = Shapes(layers=3, d_model=32, q_heads=4, kv_heads=2, head_dim=8,
               d_ff=48, vocab=64)
    key = weights.seed_key(2**32 + 77)
    tree = weights.model_weights(key, s, True, jnp.bfloat16)
    for layer in range(s.layers):
        one = jax.jit(lambda k, l: weights.layer_weights(
            k, l, s, True, jnp.bfloat16))(key, layer)
        for a, b in zip(jax.tree.leaves(one),
                        jax.tree.leaves(jax.tree.map(lambda x: x[layer],
                                                     tree["blocks"]))):
            assert a.dtype == b.dtype
            np.testing.assert_array_equal(np.asarray(a, np.float32),
                                          np.asarray(b, np.float32))
    g = weights.global_weights(key, s, True, jnp.bfloat16)
    np.testing.assert_array_equal(np.asarray(g["embed"]["head"], np.float32),
                                  np.asarray(tree["embed"]["head"],
                                             np.float32))


def test_weights_differ_by_seed_and_keep_their_amplitude():
    s = Shapes(layers=2, d_model=64, q_heads=4, kv_heads=2, head_dim=16,
               d_ff=64, vocab=128)
    a = weights.model_weights(weights.seed_key(1), s, False, jnp.bfloat16)
    b = weights.model_weights(weights.seed_key(2), s, False, jnp.bfloat16)
    wq = np.asarray(a["blocks"]["attn"]["wq"], np.float32)
    assert not np.array_equal(wq, np.asarray(b["blocks"]["attn"]["wq"],
                                             np.float32))
    assert np.abs(wq).max() <= 2.0 ** -5
    assert 0.015 < wq.std() < 0.021             # uniform: 2**-5 / sqrt(3)
    scale = np.asarray(a["blocks"]["ln1"]["scale"])
    assert scale.dtype == np.float32 and np.abs(scale - 1).max() <= 0.125
    assert "bias" not in a["blocks"]["ln1"]
