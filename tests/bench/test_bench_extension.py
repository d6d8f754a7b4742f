"""A later change adds a configuration, a traffic mix, a cell and a
per-layer metric as new files plus new entries, and the harness runs them
with no edit to any file it already had."""
import hashlib
import json
import os
import pathlib
import shutil
import subprocess
import sys
import textwrap

from conftest import CPU_DEVICE, DATA, ROOT

METRIC = '''"""Tokens committed in the window (an extension's own metric)."""


def read(ctx):
    return float(ctx.window.tokens())
'''


def digest(root: pathlib.Path) -> dict:
    return {str(p.relative_to(root)): hashlib.sha256(p.read_bytes()
                                                      ).hexdigest()
            for p in sorted(root.rglob("*")) if p.is_file()
            and "__pycache__" not in p.parts}


def test_new_cell_runs_from_new_files_only(tmp_path):
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    before = digest(tmp_path / "bench")

    b = tmp_path / "bench"
    cfg = json.loads((DATA / "lm_smoke.json").read_text())
    cfg["name"] = "ext_lm"
    (b / "configs" / "ext_lm.json").write_text(json.dumps(cfg))
    shutil.copy(DATA / "lm_smoke_mix.json", b / "traffic" / "ext_mix.json")
    (b / "metrics" / "ext_tokens.py").write_text(METRIC)
    (b / "limits" / "ext.cell.json").write_text(json.dumps(
        {"max_logit_gap": 0.05, "sample_tokens": 16}))
    bench = json.loads((tmp_path / "BENCHMARK.json").read_text())
    bench["configs"].append({"name": "ext_lm", "source": "test",
                             "file": "bench/configs/ext_lm.json",
                             "reduced": [], "why": "extension test"})
    bench["workloads"].append({"name": "ext.cell", "config": "ext_lm",
                               "traffic": "ext_mix", "chips": 1,
                               "why": "extension test"})
    for m in bench["end_to_end"]:
        if m["name"] == "out_tok_per_s":
            m["workloads"].append("ext.cell")
    bench["per_layer"].append({"name": "ext_tokens", "unit": "tokens",
                               "better": "higher", "source": "host_clock",
                               "layer": "Engine", "moves": "out_tok_per_s",
                               "workloads": ["ext.cell"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))

    script = textwrap.dedent(f"""
        import json, sys
        sys.path[:0] = [{str(tmp_path)!r}, {str(ROOT / "src")!r}]
        from bench import run
        assert run.ROOT == __import__("pathlib").Path({str(tmp_path)!r})
        outs = [run.run_loaded(*run.cell_files("ext.cell"), 5, 1.0, t,
                               device=dict({CPU_DEVICE!r}))
                for t in (False, True)]
        print(json.dumps(outs))
    """)
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run([sys.executable, "-c", script], env=env,
                          capture_output=True, text=True, timeout=600,
                          cwd=tmp_path)
    assert proc.returncode == 0, proc.stderr[-3000:]
    plain, traced = json.loads(proc.stdout.strip().splitlines()[-1])
    assert set(plain["metrics"]) == {"out_tok_per_s", "setup_s"}
    assert set(traced["metrics"]) == {"ext_tokens"}
    assert traced["metrics"]["ext_tokens"]["value"] > 0
    assert plain["correct"] and traced["correct"]

    after = digest(tmp_path / "bench")
    assert {k: after[k] for k in before} == before
    assert set(after) - set(before) == {
        "configs/ext_lm.json", "traffic/ext_mix.json",
        "metrics/ext_tokens.py", "limits/ext.cell.json"}
