"""Roofline and MFU arithmetic against values worked by hand at granite
widths, and the peaks table."""
import json

import pytest

from bench import flops, model

GRANITE = model.dims(model.load_config("granite-moe-1b-a400m"))


def test_expert_bytes():
    # 3 matrices of 1024 x 512 at 2 bytes.
    assert flops.expert_hi_bytes(GRANITE) == 3_145_728
    # Per matrix: 1024*512/2 code bytes + (K/64)*N*2 scale bytes
    # = 262144 + 16384 (gate and up: K 1024, N 512; down: K 512, N 1024).
    assert flops.expert_lo_bytes(GRANITE, 4, 64) == 835_584


def test_moe_ffn_work_and_least_time():
    fwd = [{"routed": 256, "active_hi": 100, "active_lo": 400}]
    w = flops.moe_ffn_work(GRANITE, fwd, 4, 64)
    # 256 assignments x 2 x (2*1024*512 + 512*1024) FLOPs.
    assert w["flops"] == 805_306_368
    # 100 hi experts + 400 lo experts + 256 x 2 x (2*1024 + 2*512) bytes.
    assert w["bytes"] == 314_572_800 + 334_233_600 + 1_572_864
    peaks = flops.load_peaks("TPU v5 lite")
    t = flops.least_seconds(w, peaks)
    assert t["bound"] == "memory"
    assert t["seconds"] == pytest.approx(650_379_264 / 819e9, rel=1e-12)


def test_flops_per_token():
    # Per layer at context 0: projections 2*(3 * 1024*1024) = 6291456,
    # router 2*1024*32 = 65536, experts 8*2*3*1024*512 = 25165824;
    # 24 layers, plus the head 2*1024*49155 = 100669440.
    base = 24 * (6_291_456 + 65_536 + 25_165_824) + 100_669_440
    assert flops.flops_per_token(GRANITE, 0) == base == 857_217_024
    # Scores and values: 4 * ctx * 16 heads * 64 per layer.
    assert flops.flops_per_token(GRANITE, 100) == base + 100 * 24 * 4096
    # Positions 0..3 attend to 1..4 positions: 10 in all.
    assert flops.prompt_flops(GRANITE, 0, 4) == 4 * base + 10 * 98_304


def test_unknown_device_raises():
    with pytest.raises(KeyError):
        flops.load_peaks("TPU v9 imaginary")


def test_peaks_have_a_source_and_the_v5e_row():
    table = json.loads(flops.PEAKS_FILE.read_text())
    assert "TPU v5e" in table["source"]
    v5e = table["devices"]["TPU v5 lite"]
    assert v5e["bf16_flops"] == 197e12 and v5e["int8_ops"] == 393e12
    assert v5e["hbm_bytes"] == 16e9 and v5e["hbm_bytes_per_s"] == 819e9
