"""Pin every assigned architecture dimension to the task sheet —
config drift fails loudly."""

import pytest

import repro.configs as C

# (name, family, L, d_model, H, Hkv, d_ff, vocab, extras)
ASSIGNED = [
    ("whisper-tiny", "encdec", 4, 384, 6, 6, 1536, 51865,
     dict(n_enc_layers=4, enc_ctx=1500, norm="ln", mlp="gelu",
          use_rope=False)),
    ("mixtral-8x22b", "moe", 56, 6144, 48, 8, 16384, 32768,
     dict(window=4096)),
    ("arctic-480b", "moe", 35, 7168, 56, 8, 4864, 32000, {}),
    ("qwen2-vl-2b", "vlm", 28, 1536, 12, 2, 8960, 151936,
     dict(mrope_sections=(16, 24, 24), qkv_bias=True)),
    ("qwen3-0.6b", "dense", 28, 1024, 16, 8, 3072, 151936,
     dict(qk_norm=True)),
    ("qwen1.5-32b", "dense", 64, 5120, 40, 40, 27392, 152064,
     dict(qkv_bias=True)),
    ("granite-20b", "dense", 52, 6144, 48, 1, 24576, 49152, {}),
    ("granite-3-8b", "dense", 40, 4096, 32, 8, 12800, 49155, {}),
    ("zamba2-1.2b", "hybrid", 36, 2048, 32, 32, 8192, 32000,
     dict(attn_every=6)),
    ("mamba2-2.7b", "ssm", 64, 2560, 1, 1, 0, 50280, dict(norm_eps=1e-5)),
    ("granite-4.0-h-micro", "hybrid", 40, 2048, 32, 8, 8192, 100352,
     dict(head_dim=64, use_rope=False, tie_embeddings=True, norm_eps=1e-5,
          embedding_multiplier=12.0, attention_multiplier=0.015625,
          residual_multiplier=0.22, logits_scaling=8.0, layer_period=10)),
]


@pytest.mark.parametrize("name,family,L,d,h,hkv,ff,vocab,extra", ASSIGNED)
def test_assigned_dims(name, family, L, d, h, hkv, ff, vocab, extra):
    cfg = C.get_config(name)
    assert cfg.family == family
    assert cfg.n_layers == L
    assert cfg.d_model == d
    assert cfg.n_heads == h
    assert cfg.n_kv_heads == hkv
    assert cfg.d_ff == ff
    assert cfg.vocab == vocab
    for k, v in extra.items():
        assert getattr(cfg, k) == v, (name, k)
    assert cfg.padded_vocab % cfg.vocab_pad_to == 0
    assert cfg.padded_vocab >= cfg.vocab


def test_moe_ssm_extras():
    mix = C.get_config("mixtral-8x22b").moe
    assert (mix.n_experts, mix.top_k) == (8, 2)
    arc = C.get_config("arctic-480b").moe
    assert (arc.n_experts, arc.top_k) == (128, 2)
    assert arc.dense_ff > 0                       # dense residual branch
    zam = C.get_config("zamba2-1.2b").ssm
    assert zam.d_state == 64
    mam = C.get_config("mamba2-2.7b").ssm
    assert mam.d_state == 128
    assert C.get_config("zamba2-1.2b").shared_attn_lora_rank > 0
    gr = C.get_config("granite-4.0-h-micro")
    assert (gr.ssm.d_state, gr.ssm.head_dim, gr.ssm.expand, gr.ssm.chunk,
            gr.ssm.n_groups, gr.ssm.conv_width) == (128, 64, 2, 256, 1, 4)
    # published layer_types: attention at 5, 15, 25, 35, Mamba-2 elsewhere
    assert [i for i, t in enumerate(gr.layer_types)
            if t == "attention"] == [5, 15, 25, 35]
    assert gr.layer_types.count("mamba") == 36


def test_reduced_interleaved_keeps_both_layer_kinds():
    red = C.get_config("granite-4.0-h-micro", reduced=True)
    assert set(red.layer_types) == {"mamba", "attention"}
    assert len(red.layer_types) == red.n_layers
    assert red.n_layers // red.layer_period >= 2     # the period scan runs


def test_every_arch_has_reduced():
    for name in C.ARCH_NAMES:
        red = C.get_config(name, reduced=True)
        assert red.family == C.get_config(name).family
        assert red.d_model <= 128
        assert red.vocab <= 1024


def test_shape_cells():
    from repro.configs.base import SHAPES
    got = {(s.name, s.kind, s.seq_len, s.global_batch) for s in SHAPES}
    assert got == {
        ("train_4k", "train", 4096, 256),
        ("prefill_32k", "prefill", 32768, 32),
        ("decode_32k", "decode", 32768, 128),
        ("long_500k", "decode", 524288, 1),
    }


def test_long500k_applicability_table():
    """DESIGN §6: exactly the ssm/hybrid families and windowed mixtral
    run long_500k (granite-4.0-h-micro is a hybrid: 36 of its 40 layers
    hold constant-size state)."""
    from repro.configs.base import get_shape
    from repro.launch import specs as S
    cell = get_shape("long_500k")
    runs = {n for n in C.ARCH_NAMES
            if S.applicable(C.get_config(n), cell)[0]}
    assert runs == {"mamba2-2.7b", "zamba2-1.2b", "mixtral-8x22b",
                    "granite-4.0-h-micro"}
