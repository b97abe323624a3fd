"""FLOP and byte functions against hand counts."""
import pytest

from perfbench import costs, harness, peaks


def bert():
    return harness.load_json(harness.HERE, "configs", "bert_base.json")


def mistral():
    return harness.load_json(harness.HERE, "configs", "mistral_7b.json")


def test_one_bert_layer_by_hand():
    cfg = bert()
    # per token: q, k, v, out projections 4 x 768 x 768 MACs, FFN
    # 2 x 768 x 3072 MACs = 7,077,888 MACs; attention at 512 valid
    # tokens: 512 x 768 MACs for scores and as many for the mix
    v = 512
    macs = v * (4 * 768 * 768 + 2 * 768 * 3072) + 2 * v * v * 768
    assert costs.bert_layer_flops(cfg, v) == 2 * macs
    assert costs.bert_encoder_matmul_params(cfg) == 12 * 7077888
    assert costs.bert_mlm_head_params(cfg) == 768 * 768 + 768 * 30522


def test_bert_step_is_three_forwards_and_counts_the_head_at_masks():
    cfg = bert()
    work = {"tokens": 1000, "masked": 150, "valid_sq": 400000,
            "sequences": 3}
    fwd = (2 * 84934656 * 1000 + 4 * 768 * 12 * 400000
           + 2 * (589824 + 23440896) * 150 + 2 * (589824 + 1536) * 3)
    assert costs.bert_pretrain_step(cfg, work)["flops"] == 3 * fwd


def test_flash_attention_train_by_hand():
    cfg = bert()
    work = {"tokens": 512, "valid_sq": 512 * 512}
    c = costs.flash_attention_train(cfg, work)
    # 6 matmuls of 2 x 512 x 512 x 64 FLOPs x 12 heads x 12 layers
    assert c["flops"] == 6 * 2 * 512 * 512 * 64 * 12 * 12
    # q, k, v, o forward + q, k, v, o, do, dq, dk, dv backward, bf16
    assert c["bytes"] == 12 * 512 * 768 * 2 * 12
    t, bound = costs.roofline_seconds(c, peaks.peaks_for("TPU v5 lite"))
    assert bound == "compute"


def test_one_decoder_layer_by_hand():
    cfg = mistral()
    # wq, wo 4096 x 4096; wk, wv 1024 x 4096; gate, up, down 4096 x 14336
    params = 2 * 4096 * 4096 + 2 * 1024 * 4096 + 3 * 4096 * 14336
    assert params == 218103808
    assert costs.decoder_layer_params(cfg) == params
    assert costs.decoder_layer_flops(cfg, 1000) \
        == 2 * params + 2 * 2 * 1000 * 32 * 128


def test_flash_decode_paged_by_hand():
    cfg = mistral()
    # one token of context in one layer: K and V, 8 heads x 128, bf16
    assert costs.kv_bytes_per_token_layer(cfg) == 2 * 8 * 128 * 2
    work = {"context_tokens": 10000}
    c = costs.flash_decode_paged(cfg, work)
    assert c["bytes"] == 4096 * 16 * 10000
    assert c["flops"] == 2 * 2 * 32 * 128 * 16 * 10000
    t, bound = costs.roofline_seconds(c, peaks.peaks_for("TPU v5 lite"))
    assert bound == "hbm"
    assert t == pytest.approx(4096 * 16 * 10000 / 819e9)


def test_unknown_device_kind_is_an_error():
    with pytest.raises(KeyError):
        peaks.peaks_for("TPU v99")
