"""Work counts and the peak table, against hand-worked numbers."""
import pytest

from bench import peaks, work

# a small decoder: d=8, 2 heads of 4, 1 KV head, d_ff 16, vocab 10, 2 layers
SMALL = dict(n_layers=2, d_model=8, n_heads=2, n_kv_heads=1, head_dim=4,
             d_ff=16, vocab_size=10, dtype="bfloat16")


def test_decoder_counts():
    # per layer: wq 8*2*4=64, wk 8*1*4=32, wv 32, wo 2*4*8=64, ffn 3*8*16=384
    # = 576; two layers 1152; LM head 10*8 = 80
    assert work.matmul_params(SMALL) == 1232
    assert work.weight_bytes(SMALL) == 2464
    # keys and values: 2 layers * 2 * 1 head * 4 * 2 bytes
    assert work.kv_bytes_per_token(SMALL) == 32
    # 2*1232 + 4 * 2 layers * 2 heads * 4 * ctx(3)
    assert work.token_flops(SMALL, 3) == 2464 + 192
    # prompt of 3: 3 tokens of matmuls + attention over 1+2+3 keys
    assert work.prefill_flops(SMALL, 3) == 3 * 2464 + 64 * 6
    # 2 rows holding 5 live positions: weights + 5 positions read + 2 written
    assert work.decode_step_bytes(SMALL, 2, 5) == 2464 + 32 * 5 + 32 * 2


def test_route_counts():
    flat = work.flat_route(4, 100, 8, 3200)
    assert flat == {"flops": 2 * 4 * 100 * 8, "bytes": 3200}
    lay = {"dim": 8, "nprobe": 2, "slab_k": 16, "n_slabs": 5,
           "slab_row_bytes": 32}
    ivf = work.ivf_route(3, lay)
    assert ivf["flops"] == 2 * 3 * (5 + 32) * 8
    assert ivf["bytes"] == 4 * 5 * 8 + 3 * 32 * 32


def test_roofline_takes_the_larger_bound():
    p = peaks.peaks("TPU v5 lite")
    mem = {"flops": 1.0, "bytes": 819e9}
    assert work.roofline_s(mem, p) == pytest.approx(1.0)
    comp = {"flops": 197e12 * 2, "bytes": 1.0}
    assert work.roofline_s(comp, p) == pytest.approx(2.0)


def test_unknown_device_kind_is_an_error():
    with pytest.raises(KeyError):
        peaks.peaks("TPU v9 imaginary")
