"""The dense model module (``bench/models/dense.py``): its weights, its
reference and its counts read what the benchmark read before the dense
pieces became a module, and the client's per-lane record carries what
the counts need."""
import hashlib

import jax
import numpy as np
import pytest

from bench import reference, serve, spec, weights

dense = spec.model("dense")

# ---------------------------------------------------------------------------
# Numbers read from the benchmark before the move, at the parent commit
# ---------------------------------------------------------------------------
SMALL = {"num_hidden_layers": 3, "hidden_size": 32, "num_attention_heads": 4,
         "num_key_value_heads": 2, "head_dim": 8, "intermediate_size": 48,
         "vocab_size": 64, "rope_theta": 10000.0, "rms_norm_eps": 1e-6,
         "torch_dtype": "bfloat16"}
# sha256 over (path, dtype, bytes) of every leaf of the served tree
WEIGHTS_SHA256 = {
    "layernorm":
        "f0527ffd3183791e884da66dbe1eebea4992e49229866cb3023a289ac024152e",
    "rmsnorm":
        "33b2de767b2299fc72210f7d433e5f4e5417a747b0c9debb24b0c346df4ec8cb",
}
# forward over a fixed packed batch: sums of the largest logits, of the
# served tokens' logits and of the argmaxes; served_gaps with the control
FORWARD = {
    "layernorm": (67.76110975444317, -5.291965468320996, 5833,
                  {"served_gap": 0.5170972347259521, "served_tokens": 34,
                   "control_gap": 0.005928710103034973}),
    "rmsnorm": (68.76585274934769, -9.166342264972627, 2885,
                {"served_gap": 0.5193461179733276, "served_tokens": 34,
                 "control_gap": 0.0015386790037155151}),
}
# (decode_flops, paged_attn_bytes, attn_flops) at (tokens, attended)
COUNTS = {
    ("yi-6b", 3, 1506): (35581329408, 98697216, 789577728),
    ("yi-6b", 4096, 5_000_000): (50123778293760, 327680000000,
                                 2621440000000),
    ("stablelm-3b", 3, 1506): (16491479040, 493486080, 493486080),
    ("stablelm-3b", 4096, 5_000_000): (23480993054720, 1638400000000,
                                       1638400000000),
}

YI = {"num_hidden_layers": 32, "hidden_size": 4096,
      "num_attention_heads": 32, "num_key_value_heads": 4, "head_dim": 128,
      "intermediate_size": 11008, "vocab_size": 64000}
STABLELM = {"num_hidden_layers": 32, "hidden_size": 2560,
            "num_attention_heads": 32, "num_key_value_heads": 32,
            "head_dim": 80, "intermediate_size": 6912, "vocab_size": 50304}
MODELS = {"yi-6b": YI, "stablelm-3b": STABLELM}


def _digest(tree) -> str:
    h = hashlib.sha256()
    for path, leaf in sorted(jax.tree_util.tree_leaves_with_path(tree),
                             key=lambda pl: jax.tree_util.keystr(pl[0])):
        a = np.asarray(leaf)
        h.update(jax.tree_util.keystr(path).encode())
        h.update(str(a.dtype).encode())
        h.update(a.tobytes())
    return h.hexdigest()


def _ticks(lanes):
    """One tick holding the given (tokens, context) lanes."""
    return [serve.Tick(0.0, 1.0, max(n for n, _ in lanes), list(lanes))]


@pytest.mark.parametrize("norm", ["layernorm", "rmsnorm"])
def test_weights_are_the_bits_served_before(norm):
    tree = dense.weights(weights.seed_key(2**32 + 77), {**SMALL, "norm": norm})
    assert _digest(tree) == WEIGHTS_SHA256[norm]


@pytest.mark.parametrize("norm", ["layernorm", "rmsnorm"])
def test_reference_reads_what_it_read_before(norm):
    model = {**SMALL, "num_hidden_layers": 2, "norm": norm,
             "partial_rotary_factor": 0.5}
    rng = np.random.default_rng(5)
    seqs = [(rng.integers(0, 64, n).astype(np.int32),
             rng.integers(0, 64, m).astype(np.int32))
            for n, m in ((12, 5), (30, 9), (7, 20))]
    tokens, target, _ = reference.pack(seqs, rows=4, min_len=64)
    mx, am, picked = dense.forward(model, weights.seed_key(9), tokens,
                                   target[:, None])
    mx_sum, picked_sum, am_sum, gaps = FORWARD[norm]
    # XLA's CPU threads may order a float32 sum differently: an ulp
    # (6e-8) an element at most, over 256 elements
    assert float(mx.astype(np.float64).sum()) == pytest.approx(mx_sum,
                                                               abs=1e-4)
    assert float(picked.astype(np.float64).sum()) == pytest.approx(
        picked_sum, abs=1e-4)
    assert int(am.astype(np.int64).sum()) == am_sum
    got = reference.served_gaps(dense.forward, model, 9, seqs, control=True)
    assert got == pytest.approx(gaps, abs=1e-6)


@pytest.mark.parametrize("name, tokens, attended", list(COUNTS))
def test_counts_are_the_counts_before(name, tokens, attended):
    # lanes of one token each from a context that sums to ``attended``
    ctx = attended // tokens - 1
    lanes = [(1, ctx)] * (tokens - 1) \
        + [(1, attended - tokens * (ctx + 1) + ctx)]
    ticks = _ticks(lanes)
    assert dense.attended(ticks) == attended
    model = MODELS[name]
    assert (dense.decode_flops(model, ticks),
            dense.paged_attn_bytes(model, ticks),
            dense.attn_flops(model, ticks)) == COUNTS[name, tokens, attended]


# ---------------------------------------------------------------------------
# Hand counts
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("model, per_layer, head", [
    # q 4096x4096 + k,v 2x 4096x512 + o 4096x4096 + 3x 4096x11008
    (YI, 4096 * 4096 + 2 * 4096 * 512 + 4096 * 4096 + 3 * 4096 * 11008,
     4096 * 64000),
    # MHA: q,k,v,o 4x 2560x2560 + 3x 2560x6912
    (STABLELM, 4 * 2560 * 2560 + 3 * 2560 * 6912, 2560 * 50304),
], ids=["yi-6b", "stablelm-3b"])
def test_matmul_flops_match_hand_counts(model, per_layer, head):
    assert dense.matmul_params(model) == 32 * per_layer + head
    assert dense.matmul_flops_per_token(model) == 2 * (32 * per_layer + head)


@pytest.mark.parametrize("name", sorted(MODELS))
def test_backlog_bounds_are_the_matmuls_and_one_weight_read(name):
    """What the backlog test reads of the module: a prompt token's
    matmul FLOPs, and every matrix read once per decode step in bf16,
    whatever the number of lanes."""
    model = {**MODELS[name], "torch_dtype": "bfloat16"}
    assert dense.prefill_flops_per_token(model) == \
        2 * dense.matmul_params(model)
    for lanes in (1, 32):
        assert dense.weight_bytes_per_step(model, lanes) == \
            2 * dense.matmul_params(model)


def test_yi_matmul_params_are_the_published_size_less_the_embedding():
    # 6.06e9 parameters in all; the embedding table (262M) is a gather
    assert dense.matmul_params(YI) == 5_798_625_280


@pytest.mark.parametrize("model, kv_bytes", [
    (YI, 2 * 4 * 128 * 2),            # 2 KiB per token per layer
    (STABLELM, 2 * 32 * 80 * 2),      # 10 KiB per token per layer
], ids=["yi-6b", "stablelm-3b"])
def test_kv_bytes_and_attention_flops(model, kv_bytes):
    # three tokens attending 333, 333 and 334 cached tokens: 1,000
    ticks = _ticks([(1, 332), (2, 332)])
    assert dense.attended(ticks) == 333 + 333 + 334
    assert dense.kv_bytes_per_token_layer(model) == kv_bytes
    assert dense.paged_attn_bytes(model, ticks) == 1000 * kv_bytes * 32
    assert dense.attn_flops(model, ticks) == \
        4 * 32 * model["head_dim"] * 1000 * 32
    assert dense.decode_flops(model, ticks) == \
        3 * dense.matmul_flops_per_token(model) \
        + dense.attn_flops(model, ticks)


# ---------------------------------------------------------------------------
# Weights
# ---------------------------------------------------------------------------
def test_stacked_weights_equal_the_reference_draws_bit_for_bit():
    """The program's tree (one jitted call) and the reference's
    layer-by-layer draws hold the same numbers."""
    model = {**SMALL, "norm": "layernorm"}
    a = dense.Arch.from_config(model)
    key = weights.seed_key(2**32 + 77)
    tree = dense.weights(key, model)
    for layer in range(a.shapes.layers):
        one = jax.jit(lambda k, l: dense.layer_weights(k, l, a))(key, layer)
        for x, y in zip(jax.tree.leaves(one),
                        jax.tree.leaves(jax.tree.map(lambda v: v[layer],
                                                     tree["blocks"]))):
            assert x.dtype == y.dtype
            np.testing.assert_array_equal(np.asarray(x, np.float32),
                                          np.asarray(y, np.float32))
    g = dense.global_weights(key, a)
    np.testing.assert_array_equal(np.asarray(g["embed"]["head"], np.float32),
                                  np.asarray(tree["embed"]["head"],
                                             np.float32))


def test_weights_differ_by_seed_and_keep_their_amplitude():
    model = {**SMALL, "num_hidden_layers": 2, "hidden_size": 64,
             "head_dim": 16, "intermediate_size": 64, "vocab_size": 128,
             "norm": "rmsnorm"}
    a = dense.weights(weights.seed_key(1), model)
    b = dense.weights(weights.seed_key(2), model)
    wq = np.asarray(a["blocks"]["attn"]["wq"], np.float32)
    assert not np.array_equal(wq, np.asarray(b["blocks"]["attn"]["wq"],
                                             np.float32))
    assert np.abs(wq).max() <= 2.0 ** -5
    assert 0.015 < wq.std() < 0.021             # uniform: 2**-5 / sqrt(3)
    scale = np.asarray(a["blocks"]["ln1"]["scale"])
    assert scale.dtype == np.float32 and np.abs(scale - 1).max() <= 0.125
    assert "bias" not in a["blocks"]["ln1"]


def test_the_stablelm_file_names_the_dense_module():
    cell = spec.load_cell("stablelm3b.longdoc")
    assert cell.config["module"] == "dense"
    fields = cell.model.arch_fields(cell.config["model"])
    assert fields["num_experts"] == 0 and fields["family"] == "dense"
    assert cell.model is dense        # one module object, however found


def test_the_dense_module_refuses_another_family(monkeypatch):
    """A registry entry with the file's sizes but another family would
    be served by another program module: the registry check stops it."""
    import dataclasses
    from repro.models import registry
    cell = spec.load_cell("stablelm3b.longdoc")
    assert serve.arch_config(cell.config, dense).family == "dense"
    moe = dataclasses.replace(registry.get_config("stablelm-3b"),
                              family="moe")
    monkeypatch.setattr(registry, "get_config", lambda name: moe)
    with pytest.raises(ValueError, match="differs from the configuration"):
        serve.arch_config(cell.config, dense)


# ---------------------------------------------------------------------------
# The client's per-lane record
# ---------------------------------------------------------------------------
class _Req:
    def __init__(self, decode_len):
        self.due_s, self.decode_len = 0.0, decode_len


class _State:
    def __init__(self, prompt_len):
        self.prompt = np.zeros(prompt_len, np.int32)
        self.tokens_out, self.done = [], False


class _Engine:
    def __init__(self):
        self.fused_horizons = []


def _observe(client, eng, grow, horizon=None):
    """One tick in which request i gains ``grow[i]`` tokens."""
    before = client.snapshot(0.0)
    nh = len(eng.fused_horizons)
    for i, n in grow.items():
        client.states[i].tokens_out += [0] * n
    if horizon:
        eng.fused_horizons.append(horizon)
    client.observe(before, 0.0, 1.0, nh, eng)
    return client.ticks[-1]


def _old_attended(tick_lanes):
    """The sum the client kept before the per-lane record."""
    return sum(dec * ctx + dec * (dec + 1) // 2 for dec, ctx in tick_lanes)


def test_per_lane_record_reduces_to_the_attended_sum():
    prompts = [100, 37, 512]
    client = serve.Client([_Req(64) for _ in prompts],
                          [_State(p) for p in prompts])
    eng = _Engine()
    # a prefill's first token: nothing decoded
    t = _observe(client, eng, {0: 1})
    assert t.lanes == [] and t.steps == 0
    # a one-step decode: the token fed back attends the prompt and itself
    t = _observe(client, eng, {0: 1})
    assert t.lanes == [(1, 100)] and t.steps == 1
    assert dense.attended([t]) == 101 == _old_attended(t.lanes)
    # a fused tick of 8 steps: lane 0 decodes 8; lane 1 gets its
    # prefill's token and decodes 7; lane 2 decodes 4 and stops
    client.states[2].tokens_out += [0] * 5
    t = _observe(client, eng, {0: 8, 1: 8, 2: 4}, horizon=8)
    assert t.steps == 8
    assert t.lanes == [(8, 101), (7, 37), (4, 516)]
    per_token = list(range(102, 110)) + list(range(38, 45)) \
        + list(range(517, 521))
    assert dense.attended([t]) == sum(per_token) == _old_attended(t.lanes)
    assert dense.decode_flops(STABLELM, [t]) == \
        19 * dense.matmul_flops_per_token(STABLELM) \
        + 4 * 32 * 80 * sum(per_token) * 32

    def windowed(ticks, window):
        """A count for a layer of sliding window ``window``: a token
        attends at most the window."""
        return sum(min(ctx + i, window) for k in ticks
                   for n, ctx in k.lanes for i in range(1, n + 1))
    assert windowed([t], 104) == sum(min(c, 104) for c in per_token)
    assert windowed([t], 104) == 102 + 103 + 6 * 104 + sum(range(38, 45)) \
        + 4 * 104


def test_readers_count_through_the_cells_module():
    """``step.decode_mfu`` and ``kernel.paged_attn_roofline`` divide the
    counts of the cell's model module by the device time."""
    class Trace:
        decode_s, kernel_s, device_kind = 2.0, 0.5, "TPU v5 lite"

    class Run:
        cell = spec.load_cell("stablelm3b.longdoc")
        device_trace = Trace()
        ticks = _ticks([(8, 1000), (8, 1500)])
    model = Run.cell.config["model"]
    assert spec.reader("step.decode_mfu")(Run) == pytest.approx(
        100 * dense.decode_flops(model, Run.ticks) / (2.0 * 197e12))
    # 16 tokens over 2,500 context: the K/V bytes bound the kernel
    least = dense.paged_attn_bytes(model, Run.ticks) / 819e9
    assert least > dense.attn_flops(model, Run.ticks) / 197e12
    assert spec.reader("kernel.paged_attn_roofline")(Run) == \
        pytest.approx(100 * least / 0.5)
