import pytest

from bench import harness


def test_unknown_device_kind_is_an_error():
    with pytest.raises(ValueError, match="no peaks"):
        harness.peaks("TPU v99 imaginary")
    with pytest.raises(ValueError):
        harness.peaks("_source")


def test_known_kind_has_its_peaks():
    p = harness.peaks("TPU v5 lite")
    assert p["bf16_flops_per_s"] == 197e12 and p["hbm_bytes_per_s"] == 819e9


def test_no_tpu_exits_without_a_result(capsys):
    # the tests run on the CPU: the run must refuse, not fall back
    with pytest.raises(SystemExit) as e:
        harness.main(["--workload", "seeds.wave1024", "--seed", "1", "--seconds", "1"])
    assert "no TPU" in str(e.value.code)
    assert capsys.readouterr().out == ""
