"""The harness finds a cell's pieces by name: a new traffic mix, a new
per-layer metric and a new model with a module of its own are new
files plus new BENCHMARK.json entries, with no edit to a file that is
already there."""
import dataclasses
import json
import os
import shutil

import pytest

from bench import run, spec, traffic
from bench.test_bench_harness import (TINY_CHECK, TINY_ENGINE, TINY_MIX,
                                      TINY_MODEL)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# A model the dense module cannot express: the program's ``qkv_bias``,
# with bias leaves of its own, a biased reference and its own counts.
QKV_BIAS_MODULE = '''"""A dense block with biases on q, k and v, as the program's
``qkv_bias`` serves them: the dense module's leaves and pieces, and
three bias leaves added after the projections."""
import functools

import jax
import jax.numpy as jnp
import numpy as np

from bench import spec
from bench import weights as W
from bench.reference import mm

dense = spec.sibling("dense", __file__)

Arch = dense.Arch
BIAS = {"bq": (16, -3), "bk": (17, -3), "bv": (18, -3)}


def arch_fields(model):
    return {**dense.arch_fields(model), "qkv_bias": True}


def layer_weights(key, layer, a):
    lw = dense.layer_weights(key, layer, a)
    s = a.shapes
    for name, heads in (("bq", s.q_heads), ("bk", s.kv_heads),
                        ("bv", s.kv_heads)):
        lw["attn"][name] = W.uniform(key, layer, *BIAS[name],
                                     (heads * s.head_dim,)).astype(a.jnp_dtype)
    return lw


@functools.partial(jax.jit, static_argnums=(1,))
def _stacked(key, a):
    out = dense.global_weights(key, a)
    out["blocks"] = jax.vmap(lambda l: layer_weights(key, l, a))(
        jnp.arange(a.shapes.layers))
    return out


def weights(key, model):
    return _stacked(key, Arch.from_config(model))


def block(x, lw, a, control):
    s = a.shapes
    lw = jax.tree.map(lambda w: w.astype(jnp.float32), lw)
    t = x.shape[0]
    h = dense.norm(x, lw["ln1"], a)
    at = lw["attn"]

    def proj(n, heads):
        y = mm(h, at["w" + n], control) + at["b" + n]
        return y.reshape(t, heads, s.head_dim)
    q = dense.rope(proj("q", s.q_heads), a)
    k = dense.rope(proj("k", s.kv_heads), a)
    x = x + mm(dense.attention(q, k, proj("v", s.kv_heads), control),
               at["wo"], control)
    h = dense.norm(x, lw["ln2"], a)
    f = lw["ffn"]
    y = jax.nn.silu(mm(h, f["w_gate"], control)) * mm(h, f["w_up"], control)
    return x + mm(y, f["w_down"], control)


@functools.partial(jax.jit, static_argnums=(2, 3))
def _layer(x, key_layer, a, control):
    key, layer = key_layer
    lw = layer_weights(key, layer, a)
    return jax.lax.map(lambda xi: block(xi, lw, a, control), x)


def forward(model, key, tokens, targets, control=False):
    a = Arch.from_config(model)
    ctl = a.dtype if control else None
    x = dense.embed(jnp.asarray(tokens), key, a)
    for layer in range(a.shapes.layers):
        x = _layer(x, (key, jnp.asarray(layer, jnp.int32)), a, ctl)
    mx, am, picked = dense.head(x, jnp.asarray(targets), key, a, ctl)
    return np.asarray(mx), np.asarray(am), np.asarray(picked)


def decode_flops(model, ticks):
    """The dense count, and the three bias adds of every decoded token."""
    s = dense.Shapes.from_config(model)
    tokens = sum(n for t in ticks for n, _ in t.lanes)
    return dense.decode_flops(model, ticks) \\
        + tokens * s.layers * (s.q_heads + 2 * s.kv_heads) * s.head_dim


attn_flops = dense.attn_flops
paged_attn_bytes = dense.paged_attn_bytes
prefill_flops_per_token = dense.prefill_flops_per_token


def weight_bytes_per_step(model, lanes):
    """The dense matrices, and the three bias vectors of every layer."""
    s = dense.Shapes.from_config(model)
    return dense.weight_bytes_per_step(model, lanes) + s.dtype_bytes \
        * s.layers * (s.q_heads + 2 * s.kv_heads) * s.head_dim
'''


@pytest.fixture
def checkout(tmp_path):
    """A copy of the benchmark's files, then a dummy mix, a dummy metric
    and a cell that uses both, and a tiny chat cell of a model with a
    module of its own, added as new files and entries only."""
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
    (root / "bench" / "models" / "qkvbias.py").write_text(QKV_BIAS_MODULE)
    (root / "bench" / "configs" / "tiny-qkvbias.json").write_text(json.dumps(
        {"name": "tiny-qkvbias", "registry": "tiny-qkvbias",
         "module": "qkvbias", "model": TINY_MODEL, "engine": TINY_ENGINE,
         "check": TINY_CHECK}))
    (root / "bench" / "traffic" / "tinychat.json").write_text(
        json.dumps(TINY_MIX))
    b["configs"].append({"name": "tiny-qkvbias", "source": "test only",
                         "file": "bench/configs/tiny-qkvbias.json",
                         "reduced": [], "why": "test only"})
    b["workloads"].append({"name": "tiny.qkvbias", "config": "tiny-qkvbias",
                           "traffic": "tinychat", "chips": 1,
                           "why": "test only"})
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


@pytest.fixture
def qkvbias_registry(monkeypatch):
    """A registry entry for the tiny biased model: Yi-6B's entry cut to
    the tiny size, with the program's q/k/v biases on."""
    from repro.models import registry
    get = registry.get_config
    tiny = dataclasses.replace(
        get("yi-6b"), name="tiny-qkvbias", num_layers=2, d_model=64,
        num_q_heads=4, num_kv_heads=2, d_head=16, d_ff=128, vocab=4096,
        rope_theta=10000.0, qkv_bias=True)
    monkeypatch.setattr(registry, "get_config",
                        lambda name: tiny if name == "tiny-qkvbias"
                        else get(name))


def test_a_model_with_a_module_of_its_own_runs_and_is_checked(
        checkout, qkvbias_registry, monkeypatch):
    """The biased model is served and checked through its own module;
    the same run is not correct when the reference leaves the bias
    out."""
    cell = spec.load_cell("tiny.qkvbias", root=str(checkout))
    assert cell.model.__file__ == str(checkout / "bench" / "models"
                                      / "qkvbias.py")
    # it builds on the dense module of its own checkout
    assert cell.model.dense is spec.model("dense", str(checkout / "bench"))
    out = run.run_cell(cell, seed=2**31 + 23, seconds=1.0, trace=False)
    assert out["correct"], out["check"]
    assert out["check"]["served_tokens"]["value"] >= 100
    assert set(out["metrics"]) == {"tpot_p90_ms", "output_tok_s", "setup_s"}
    assert out["attempted"] > 0 and out["failed"] == 0

    monkeypatch.setattr(cell.model, "forward", cell.model.dense.forward)
    out = run.run_cell(cell, seed=2**31 + 23, seconds=1.0, trace=False)
    assert not out["correct"], out["check"]
    assert out["check"]["served_gap"]["value"] > TINY_CHECK[
        "served_gap_limit"]


def test_the_dense_module_refuses_a_biased_registry_entry(
        checkout, qkvbias_registry):
    """The dense module cannot stand in for the biased one: the harness
    holds the registry to the fields the module names, and raises."""
    from bench import serve
    cell = spec.load_cell("tiny.qkvbias", root=str(checkout))
    with pytest.raises(ValueError, match="differs from the configuration"):
        serve.arch_config(cell.config, spec.model("dense"))


def test_the_backlog_bound_reads_a_new_modules_counts(checkout):
    """The backlog test's roofline reads only the module interface, so
    it runs on a model with a module of its own."""
    from bench.test_bench_yardstick import roofline_finished
    from bench import peaks
    cell = spec.load_cell("tiny.qkvbias", root=str(checkout))
    dense = spec.model("dense", str(checkout / "bench"))
    model = cell.config["model"]
    assert cell.model.weight_bytes_per_step(model, 4) > \
        dense.weight_bytes_per_step(model, 4)
    # the tiny model's work is nothing to the chip: all 3 requests of
    # the backlog mix finish within a second
    cell = dataclasses.replace(cell, traffic=json.loads(
        (checkout / "bench" / "traffic" / "dummy.json").read_text()))
    assert roofline_finished(cell, peaks.peak_for("TPU v5 lite"), 1.0) == 3


def test_a_module_without_the_whole_interface_is_refused(checkout):
    """``spec.model`` names what a module lacks before any run."""
    src = (checkout / "bench" / "models" / "qkvbias.py").read_text()
    (checkout / "bench" / "models" / "partial.py").write_text(
        src.replace("def weight_bytes_per_step(", "def _unused("))
    with pytest.raises(AttributeError, match="weight_bytes_per_step"):
        spec.model("partial", str(checkout / "bench"))
