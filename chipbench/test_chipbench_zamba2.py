"""The Zamba2 yardstick: its plain reference against ``transformers``' own
Zamba2 at a tiny size (a guessed mechanism under the real name would part
them), the served model's parameter count and ``work_hybrid``'s counts
against a hand count at the published widths, and the two hybrid metrics'
readers on a synthetic run record."""
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import manifest, traffic, work, work_hybrid

HERE = os.path.dirname(os.path.abspath(__file__))
FILE = manifest.config("zamba2-7b.int8")
PEAK = work.peaks("TPU v5 lite")
# tiny widths that keep the published 12-layer prefix, both shared blocks
# and two B/C groups; the head is twice the model width over the heads
TINY = dict(hidden_size=64, attention_hidden_size=128, attention_head_dim=32,
            num_attention_heads=4, num_key_value_heads=4, ffn_hidden_size=96,
            intermediate_size=96, n_mamba_heads=8, mamba_headdim=16,
            mamba_d_state=8, adapter_rank=4, vocab_size=97, chunk_size=64)


def _tiny_weights(seed=0):
    """A tree in the served model's layout, drawn at the tiny widths, with
    A_log, dt_bias, D, the norms and the conv at spreads of their own."""
    f = dict(FILE, **TINY)
    d, a, v = f["hidden_size"], f["attention_hidden_size"], f["vocab_size"]
    h, hd, ff, r = (f["num_attention_heads"], f["attention_head_dim"],
                    f["ffn_hidden_size"], f["adapter_rank"])
    mh, p, n, g, w = (f["n_mamba_heads"], f["mamba_headdim"], f["mamba_d_state"],
                      f["mamba_ngroups"], f["mamba_d_conv"])
    inner, conv = mh * p, mh * p + 2 * g * n
    rng = np.random.default_rng(seed)

    def draw(*shape, std=0.1):
        return jnp.asarray(rng.normal(0, std, shape), jnp.float32)

    def mixer(k):
        return {"norm": {"scale": draw(k, d, std=1.0)}, "mixer": {
            "in_proj": draw(k, d, 2 * inner + 2 * g * n + mh),
            "conv_w": draw(k, w, conv, std=0.5), "conv_b": draw(k, conv),
            "A_log": draw(k, mh, std=0.5), "D": draw(k, mh, std=1.0),
            "dt_bias": draw(k, mh, std=0.5), "norm": draw(k, inner, std=1.0),
            "out_proj": draw(k, inner, d)}}

    tree = {"embed": draw(v, d, std=0.5), "final_norm": {"scale": draw(d, std=1.0)},
            "shared": {"attn_norm": {"scale": draw(2, a, std=1.0)},
                       "attn": {"wq": draw(2, a, h, hd), "wk": draw(2, a, h, hd),
                                "wv": draw(2, a, h, hd), "wo": draw(2, h, hd, d)},
                       "mlp_norm": {"scale": draw(2, d, std=1.0)},
                       "mlp": {"gate": draw(2, d, ff), "up": draw(2, d, ff),
                               "down": draw(2, ff, d)}}}
    kinds = f["layers_block_type"][:f["num_hidden_layers"]]
    seg, i = 0, 0
    while i < len(kinds):
        j = i
        while j < len(kinds) and kinds[j] == kinds[i]:
            j += 1
        for k in ([j - i] if kinds[i] == "mamba" else [1] * (j - i)):
            layer = mixer(k)
            if kinds[i] == "hybrid":
                layer.update(linear=draw(k, d, d), adapter={
                    "down": draw(k, d, r), "gate": draw(k, r, ff), "up": draw(k, r, ff)})
            tree[f"seg{seg}_{kinds[i]}"] = layer
            seg += 1
        i = j
    return f, tree


def _as_transformers(f, tree):
    """``transformers.Zamba2ForCausalLM`` holding the same weights."""
    torch = pytest.importorskip("torch")
    tr = pytest.importorskip("transformers")
    cfg = tr.Zamba2Config(
        vocab_size=f["vocab_size"], hidden_size=f["hidden_size"],
        num_hidden_layers=f["num_hidden_layers"],
        layers_block_type=f["layers_block_type"][:f["num_hidden_layers"]],
        mamba_d_state=f["mamba_d_state"], mamba_d_conv=f["mamba_d_conv"],
        mamba_expand=f["mamba_expand"], mamba_ngroups=f["mamba_ngroups"],
        n_mamba_heads=f["n_mamba_heads"], chunk_size=f["chunk_size"],
        intermediate_size=f["ffn_hidden_size"],
        # the reference's documented departure: the tanh form of GELU
        hidden_act="gelu_pytorch_tanh",
        num_attention_heads=f["num_attention_heads"],
        num_mem_blocks=f["num_mem_blocks"], adapter_rank=f["adapter_rank"],
        use_shared_attention_adapter=f["use_shared_attention_adapter"],
        use_mem_rope=f["use_mem_rope"], rope_theta=f["rope_theta"],
        rms_norm_eps=f["rms_norm_eps"], attn_implementation="eager")
    model = tr.Zamba2ForCausalLM(cfg).eval()

    def put(param, value):
        param.data.copy_(torch.tensor(np.asarray(value, np.float32)))

    def put_mixer(layer, lw):
        put(layer.input_layernorm.weight, lw["norm"]["scale"])
        mx, mw = layer.mamba, lw["mixer"]
        mx.time_step_min = 0.0  # the kernel path's dt, unclamped
        put(mx.in_proj.weight, mw["in_proj"].T)
        put(mx.conv1d.weight, np.asarray(mw["conv_w"]).T[:, None, :])
        for name in ("dt_bias", "A_log", "D"):
            put(getattr(mx, name), mw[name])
        put(mx.conv1d.bias, mw["conv_b"])
        put(mx.norm.weight, mw["norm"])
        put(mx.out_proj.weight, mw["out_proj"].T)

    m = model.model
    put(m.embed_tokens.weight, tree["embed"])
    put(m.final_layernorm.weight, tree["final_norm"]["scale"])
    segs = sorted((int(k[3:k.index("_")]), k) for k in tree if k.startswith("seg"))
    i = calls = 0
    for _, key in segs:
        for j in range(jax.tree.leaves(tree[key])[0].shape[0]):
            lw = jax.tree.map(lambda x: x[j], tree[key])
            layer = m.layers[i]
            i += 1
            if key.endswith("_mamba"):
                put_mixer(layer, lw)
                continue
            put_mixer(layer.mamba_decoder, lw)
            put(layer.linear.weight, lw["linear"].T)
            sw = jax.tree.map(lambda x: x[calls % f["num_mem_blocks"]], tree["shared"])
            block = layer.shared_transformer
            put(block.input_layernorm.weight, sw["attn_norm"]["scale"])
            for ours, theirs in (("wq", "q_proj"), ("wk", "k_proj"), ("wv", "v_proj")):
                put(getattr(block.self_attn, theirs).weight,
                    np.asarray(sw["attn"][ours]).reshape(f["attention_hidden_size"], -1).T)
            put(block.self_attn.o_proj.weight,
                np.asarray(sw["attn"]["wo"]).reshape(-1, f["hidden_size"]).T)
            put(block.pre_ff_layernorm.weight, sw["mlp_norm"]["scale"])
            ff = block.feed_forward
            put(ff.gate_up_proj.weight,
                np.concatenate([sw["mlp"]["gate"], sw["mlp"]["up"]], 1).T)
            put(ff.down_proj.weight, np.asarray(sw["mlp"]["down"]).T)
            adapter = ff.gate_up_proj_adapter_list[calls]
            put(adapter[0].weight, np.asarray(lw["adapter"]["down"]).T)
            put(adapter[1].weight,
                np.concatenate([lw["adapter"]["gate"], lw["adapter"]["up"]], 1).T)
            calls += 1
    return model


def test_reference_matches_transformers():
    """The reference's unquantized logits equal ``transformers``' Zamba2 on
    the same weights within 1e-4 (float32 on both sides, some 1e-6 apart).
    The 48 positions lie within one of transformers' SSD chunks (64): its
    CPU path sums the decay between chunks over the wrong axis, so past one
    chunk it computes another model."""
    torch = pytest.importorskip("torch")
    f, tree = _tiny_weights()
    hf = _as_transformers(f, tree)
    tokens = np.random.default_rng(1).integers(0, f["vocab_size"], 48)
    exact = dict(f, activations=None, control=None, af_format=None,
                 weight_format=None)
    ref = manifest.reference(FILE).logits(tree, jnp.asarray(tokens, jnp.int32), exact)
    with torch.no_grad():
        theirs = hf(torch.tensor(tokens)[None], use_cache=False).logits[0].numpy()
    assert np.abs(theirs).max() > 1.0  # logits that say something
    np.testing.assert_allclose(np.asarray(ref), theirs, atol=1e-4)


def test_reference_reads_the_layers_it_is_given():
    """A tree whose segments disagree with the layer list is refused."""
    f, tree = _tiny_weights()
    del tree["seg1_hybrid"]
    with pytest.raises(ValueError, match="configuration"):
        manifest.reference(FILE).logits(tree, jnp.zeros((4,), jnp.int32),
                                        dict(f, activations=None, control=None,
                                             af_format=None, weight_format=None))


def _hand_count(layers: int, hybrid: int):
    """Published widths: a mixer 78.4 M, a shared block 334.0 M, an adapter
    4.1 M, a ``linear`` 12.8 M, the embedding 114.7 M (tied head), and the
    final norm."""
    d, inner, conv, heads = 3584, 7168, 7168 + 2 * 2 * 64, 112
    mixer = (d * (2 * inner + 2 * 2 * 64 + heads) + inner * d  # in, out
             + 5 * conv + 3 * heads + inner + d)  # conv + bias, A/D/dt, norms
    shared = 3 * 7168 * 7168 + 7168 * d + 3 * d * 14336 + 7168 + d
    adapter, linear, embed = d * 128 + 128 * 2 * 14336, d * d, 32000 * d
    parts = (mixer, shared, adapter, linear, embed)
    total = (layers * mixer + 2 * shared + hybrid * (adapter + linear)
             + embed + d)
    return parts, total


def test_parameter_counts_by_hand():
    """1.758 B for the served 12 layers, 7.357 B for all 81."""
    parts, _ = _hand_count(12, 2)
    assert tuple(round(n / 1e6, 1) for n in parts) == (78.4, 334.0, 4.1, 12.8,
                                                      114.7)
    assert round(_hand_count(12, 2)[1] / 1e9, 3) == 1.758
    assert round(_hand_count(81, 13)[1] / 1e9, 3) == 7.357
    for layers, hybrid in ((12, 2), (81, 13)):
        cfg = dict(FILE, num_hidden_layers=layers)
        assert work_hybrid.param_count(cfg) == _hand_count(layers, hybrid)[1]


@pytest.mark.parametrize("layers,hybrid", [(12, 2), (81, 13)])
def test_program_holds_the_counted_parameters(layers, hybrid):
    """The served model's own tree (its specs: nothing is allocated) has
    exactly the counted parameters, at the file's depth and at the
    published one."""
    import dataclasses

    from repro.configs import get_config
    from repro.models import get_model

    cfg = dataclasses.replace(get_config(FILE["arch"]), num_layers=layers)
    assert get_model(cfg).count_params() == _hand_count(layers, hybrid)[1]


def test_work_by_hand():
    """A decoded token's FLOPs and a decode step's least bytes at the
    published widths, 12 layers."""
    matmul = (12 * (3584 * 14704 + 7168 * 3584)
              + 2 * (3 * 7168 * 7168 + 7168 * 3584 + 3 * 3584 * 14336
                     + 3584 * 128 + 128 * 28672 + 3584 * 3584)
              + 3584 * 32000)
    assert work_hybrid.matmul_params(FILE) == matmul
    per_layer_mixer = 2 * 4 * 7424 + 4 * 112 * 64 * 64
    attn = 4 * 300 * 32 * 224
    assert work_hybrid.token_flops(FILE, 300) == 2 * matmul + 12 * per_layer_mixer + 2 * attn
    state = 12 * (112 * 64 * 64 + 3 * 7424) * 4  # 23.1 MB a slot
    kv_row = 2 * 2 * 32 * 224 * 4
    assert work_hybrid.state_bytes(FILE) == state
    assert work_hybrid.decode_bytes(FILE, 3, [10, 20]) == (
        3 * matmul + 2 * state * 2 + kv_row * 30)
    assert work_hybrid.prompt_flops(FILE, [4]) == (
        4 * work_hybrid.token_flops(FILE, 0) + 4 * 32 * 224 * 2 * 10)


def _record(trace=None):
    spec = traffic.Spec(np.zeros(10, np.int32), 20)
    # prefilled token at 1.5, 8 more at 2.0; the window is (1.0, 3.0]
    return {"cfg": FILE, "t0": 1.0, "t_end": 3.0, "window_s": 2.0,
            "bursts": 2, "burst": 8, "slots": 4, "prefill_rows": 500,
            "tracks": [(spec, 0.5, [(1.5, 1), (2.0, 9)])], "peak": PEAK,
            "trace": trace}


def test_hybrid_metric_readers_by_hand():
    flops = (work_hybrid.prompt_flops(FILE, [10])
             + sum(work_hybrid.token_flops(FILE, 10 + j) for j in range(1, 9)))
    read = lambda name, rec: manifest.metric_reader(name)(rec)
    assert read("hybrid.step.mfu", _record()) == pytest.approx(
        100 * flops / 2.0 / PEAK["int8_ops"])
    trace = {"programs": {"decode_burst": [2, 0.05]}}
    least = work_hybrid.decode_bytes(FILE, 16, [10 + j for j in range(1, 9)])
    assert read("hybrid.decode_hbm_roofline", _record(trace)) == pytest.approx(
        100 * least / PEAK["hbm_bytes_per_s"] / 0.05)


def test_hybrid_metric_readers_find_nothing():
    """No decode burst in the trace, or a dense model's file: None."""
    read = lambda name, rec: manifest.metric_reader(name)(rec)
    assert read("hybrid.decode_hbm_roofline", _record({"programs": {}})) is None
    assert read("hybrid.decode_hbm_roofline", _record()) is None
    with open(os.path.join(HERE, "configs", "olmo-1b.int8.json")) as f:
        dense = dict(_record({"programs": {"decode_burst": [2, 0.05]}}),
                     cfg=json.load(f))
    assert read("hybrid.step.mfu", dense) is None
    assert read("hybrid.decode_hbm_roofline", dense) is None
