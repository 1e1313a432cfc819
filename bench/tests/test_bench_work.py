"""bench/work/spt.py against counts reckoned by hand at published widths."""

import pytest

from bench import common
from bench.work import spt


def model(name):
    return common.load_json(common.BENCH / "configs" / f"{name}.json")["model"]


def test_kept_keys_average_over_a_causal_1k_sequence():
    # L = 1024 / 8 = 128: queries 0..127 keep t + 1 keys, the rest 128
    want = (sum(range(1, 129)) + 896 * 128) / 1024
    assert spt.mean_kept(1024, model("qwen3-0.6b")) == pytest.approx(want)


@pytest.mark.parametrize("name, per_token", [
    # qwen3-0.6b, per layer and token:
    #  projections 2*1024*(2048 + 1024 + 1024 + 2048)      = 12,582,912
    #  LoRA r16: 32*(3072 + 2048 + 2048 + 3072)              =    327,680
    #  PQ codes 2*16*128*(16 + 8)                            =     98,304
    #  scores 16 heads * 2 * 16 books * 1025 / 2             =    262,400
    #  kept attention 16 * 4 * 128 * 120.0625                =    983,552
    #  router 2*1024*8                                       =     16,384
    #  FFN 4 groups * 3 * 2 * 1024 * 384                     =  9,437,184
    #  FFN LoRA 2*(32768 + 4*12288) + 4*12288 + 32768        =    245,760
    # backward: frozen once, LoRA / router / kept twice
    ("qwen3-0.6b", 28 * ((12582912 + 327680 + 98304 + 262400 + 983552
                          + 16384 + 9437184 + 245760)
                         + 12582912 + 9437184
                         + 2 * (327680 + 245760 + 16384) + 2 * 983552)
     + 2 * 2 * 1024 * 151936),
    # opt-2.7b, per layer and token:
    #  projections 2*2560*2560*4                             = 52,428,800
    #  LoRA 32*(5120)*4                                      =    655,360
    #  PQ codes 2*16*80*(32 + 32)                            =    163,840
    #  scores 32 * 2 * 10 * 1025 / 2                         =    328,000
    #  kept attention 32 * 4 * 80 * 120.0625                 =  1,229,440
    #  router 2*2560*8                                       =     40,960
    #  FFN 4 * 2 * 2 * 2560 * 1280                           = 52,428,800
    #  FFN LoRA (81920 + 4*40960) + 4*40960 + 81920          =    491,520
    ("opt-2.7b", 32 * ((52428800 + 655360 + 163840 + 328000 + 1229440
                        + 40960 + 52428800 + 491520)
                       + 52428800 + 52428800
                       + 2 * (655360 + 491520 + 40960) + 2 * 1229440)
     + 2 * 2 * 2560 * 50272),
])
def test_train_flops_per_token(name, per_token):
    conf = common.load_json(common.BENCH / "configs" / f"{name}.json")
    got = spt.train_flops_per_token(conf["model"], 1024,
                                    conf["source_config"]["vocab_size"])
    assert got == pytest.approx(per_token)


@pytest.mark.parametrize("name, flops, nbytes", [
    # 4 x 1024 tokens; bytes: q and out 2 * hq*hd, k and v 2 * hk*hd, bf16
    ("qwen3-0.6b", 4096 * (98304 + 262400 + 983552),
     4096 * 128 * (2 * 16 + 2 * 8) * 2),
    ("opt-2.7b", 4096 * (163840 + 328000 + 1229440),
     4096 * 80 * (2 * 32 + 2 * 32) * 2),
])
def test_sparse_attention_forward_call(name, flops, nbytes):
    w = spt.sparse_attention_fwd(model(name), 4, 1024)
    assert w == {"flops": pytest.approx(flops), "bytes": nbytes}


@pytest.mark.parametrize("name, flops, nbytes", [
    # weights of every group once (gated: 3 matrices), tokens in and out
    ("qwen3-0.6b", 4096 * (9437184 + 245760),
     3 * 1024 * 3072 * 2 + 2 * 4096 * 1024 * 2),
    ("opt-2.7b", 4096 * (52428800 + 491520),
     2 * 2560 * 10240 * 2 + 2 * 4096 * 2560 * 2),
])
def test_routed_ffn_forward_call(name, flops, nbytes):
    w = spt.routed_ffn_fwd(model(name), 4, 1024)
    assert w == {"flops": flops, "bytes": nbytes}


def test_least_time_is_the_larger_bound():
    peaks = {"flops_bf16": 100.0, "hbm_bytes_per_s": 10.0}
    assert spt.least_seconds({"flops": 300, "bytes": 20}, peaks) == 3.0
    assert spt.least_seconds({"flops": 100, "bytes": 50}, peaks) == 5.0
