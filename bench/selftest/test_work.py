"""Operation counts of ``bench/work.py`` against counts made by hand."""

import json

from bench import harness, work


def _cfg(name):
    return json.loads((harness.BENCH / "configs" / f"{name}.json").read_text())


def test_fpca_cnn_frame_flops_match_hand_counts():
    cfg = _cfg("fpca_cnn")
    head = harness.load_module(harness.BENCH / "configs" / "fpca_cnn.py")
    # a 560x560 sensor read in 5x5 windows at stride 5: 112 x 112 windows
    assert work.frontend_dims(cfg) == (112, 112, 75, 8)
    assert work.frontend_model_flops(cfg) == 2 * 12_544 * 75 * 8 == 15_052_800
    assert head.head_flops(cfg) == 2 * (100_352 * 64 + 64 * 2) == 12_845_312
    assert work.model_flops_per_frame(cfg, head.head_flops(cfg)) == 27_898_112


def test_fpca_cnn_kernel_work_is_unpadded():
    cfg = _cfg("fpca_cnn")
    # three basis matmul pairs, both phases: 12 N C per window
    assert 12 * 75 * 8 * 576 == 4_147_200
    per_window = 12 * 75 * 8 + 6 * 75 + 4 * 15 * 8
    assert work.kernel_flops_per_window(cfg) == per_window
    flops, nbytes = work.kernel_work(cfg, 576)
    assert flops == per_window * 576
    assert nbytes == 576 * 4 * (75 + 8) == 191_232


def test_detect_vga_frame_flops_match_hand_counts():
    cfg = _cfg("fpca_detect_vga")
    head = harness.load_module(harness.BENCH / "configs" / "fpca_detect_vga.py")
    assert work.frontend_dims(cfg) == (96, 128, 75, 8)
    assert work.frontend_model_flops(cfg) == 2 * 12_288 * 75 * 8 == 14_745_600
    trunk = 2 * 12_288 * 72 * 16
    det = 2 * 12_288 * 16 * 6
    assert head.head_flops(cfg) == trunk + det == 30_670_848
