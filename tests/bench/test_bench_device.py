"""The harness refuses what is not a TPU and any device kind missing from
the peak table; on a CPU the command exits 2 and prints no result."""
import pytest

from bench import common
from bench.common import BenchError


def test_unknown_device_kind_is_an_error():
    with pytest.raises(BenchError, match="not in bench/peaks.json"):
        common.peaks("TPU v9 imaginary")


def test_v5e_peaks_have_their_source():
    p = common.peaks("TPU v5 lite")
    assert p["bf16_flops_per_s"] == 197e12
    assert p["hbm_bytes_per_s"] == 819e9
    assert "TPU v5e" in p["source"]


def test_cpu_is_refused():
    with pytest.raises(BenchError, match="no TPU"):
        common.check_device(1)


def test_cli_on_cpu_exits_2_without_result(capsys):
    from bench import run
    cell = common.benchmark()["workloads"][0]["name"]
    rc = run.main(["--workload", cell, "--seed", "1", "--seconds", "1",
                   "--trace", "0"])
    out = capsys.readouterr()
    assert rc == 2
    assert "{" not in out.out
    assert "no TPU" in out.err


def test_unknown_names_are_errors():
    with pytest.raises(BenchError):
        common.find([{"name": "a"}], "b", "workload")
    with pytest.raises(BenchError):
        common.named_file("configs", "../BENCHMARK")
    with pytest.raises(BenchError):
        common.named_file("configs", "no_such_config")
