import pytest

from benchmark import manifest, readers


def test_gpt2_small_hand_count():
    cfg = manifest.load_config_file("gpt2-small")
    ref = manifest.family_module("references", cfg["family"])
    # per layer: 24 * 768^2 = 14,155,776; causal attention at T=1024:
    # 4 * 768 * 512.5 = 1,574,400; 12 layers: 188,762,112
    # head: 2 * 768 * 50257 = 77,194,752; forward 265,956,864; x3
    assert ref.forward_flops_per_token(cfg, 1024) == 265_956_864
    assert ref.train_flops_per_token(cfg, 1024) == 797_870_592


def _ctx(reference, busy_s=2.0):
    cfg = manifest.load_config_file("gpt2-small")
    return readers.Context(
        cell="c", chips=1, peaks=manifest.peaks_for("TPU v5 lite"),
        rounds=3, timed=[], snaps=[],
        trace={"busy_s": busy_s, "window_s": 40.0}, tokens_traced=49152,
        reference=reference, cfg=cfg, seq_len=1024)


def test_busy_mfu_is_the_familys_count_over_the_busy_seconds():
    ref = manifest.family_module("references", "transformer")
    # 797,870,592 x 49,152 tokens / (2 s x 197e12) = 9.954%
    assert readers.busy_mfu(_ctx(ref), {}) == pytest.approx(
        100 * 797_870_592 * 49152 / (2.0 * 197e12))


def test_a_family_without_a_count_reports_nothing():
    assert readers.busy_mfu(_ctx(object()), {}) is None


def test_peaks_by_exact_device_kind():
    v5e = manifest.peaks_for("TPU v5 lite")
    assert v5e["bf16_flops_per_s"] == 197e12
    assert v5e["hbm_bytes_per_s"] == 819e9
    assert v5e["hbm_bytes"] == 16e9
    for kind in ("TPU v5", "v5 lite", "cpu", "TPU v5 lite "):
        with pytest.raises(manifest.ManifestError):
            manifest.peaks_for(kind)
