"""A made-up deployment joins the benchmark as new files only.

A copy of the benchmark (its directories and ``BENCHMARK.json``) gets a
configuration that names its own reference module and its own check
module, for a kind that no accepted cell sends (``progress``), a traffic
mix that sends it, a metric reader that reads the program's own records,
a faults file, and one new configuration, workload and metric entry in
``BENCHMARK.json``. The new cell then runs through the copy's
``rehearse.py`` on the CPU, in processes of its own, beside an accepted
cell, which must read as it did; no file that the copy held before may
change.

    JAX_PLATFORMS=cpu python -m pytest -q benchmarks/chip/test_deployment.py
"""
from __future__ import annotations

import hashlib
import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]
SEED = 2**33 + 777
CELL = "toy-1k.progress"
#: an accepted cell, run in the copy once the toy has joined
ACCEPTED = "dropout-100k.dashboard"

NEW_FILES = {
    "configs/toy-1k.json": None,  # written from fuel-100k's below
    "traffic/toy-progress.json": None,  # written from the dashboard's below
    "reference_toy.py": '''\
"""Plain reference of the toy deployment: the fuel fleet's, and what a
progress read says where no round was ever submitted."""
from reference import *  # noqa: F401,F403


def progress() -> dict:
    """No round is active."""
    return {"active": 0}
''',
    "checks/progress.py": '''\
"""Progress reads: ``progress_miss``, answers that differ from the
reference's."""
KINDS = ("progress",)
LIMITS = {"progress_miss": 0}


def check(ev, items, seed, lower=False):
    miss = sum(r.body != ev.ref.progress() for _, _, r in items)
    return {"progress_miss": int(miss), "_progress_checked": len(items)}
''',
    "metrics/admitted_per_plane_step.py": '''\
"""Requests admitted per plane step, from the program's own records: its
``admitted`` counter over its ``plane.step`` spans."""
import numpy as np

PROGRAM = True


def read(ctx):
    spans = ctx.program.get("spans")
    steps = 0 if spans is None else int(np.count_nonzero(spans["name"] == "plane.step"))
    return ctx.delta("admitted") / steps if steps else None
''',
    "faults/toy-1k.py": '''\
"""The toy deployment's own fault."""


def phantom_round(sim, gw):
    """Progress reads report a round that was never submitted."""
    gw._read_progress = lambda params: {"active": 1}


FAULTS = [phantom_round]
''',
}


def _digests(root: Path) -> dict:
    return {str(p.relative_to(root)): hashlib.sha256(p.read_bytes()).hexdigest()
            for p in sorted(root.rglob("*")) if p.is_file()}


def _join(root: Path) -> dict:
    """Add the toy deployment to the copy at ``root``; returns the copy's
    BENCHMARK.json as it was."""
    bench_file = root / "BENCHMARK.json"
    before = json.loads(bench_file.read_text())
    chip = root / "benchmarks" / "chip"
    for rel, text in NEW_FILES.items():
        assert not (chip / rel).exists(), rel
        (chip / rel).parent.mkdir(parents=True, exist_ok=True)
        if rel.startswith("configs/"):
            config = json.loads((chip / "configs" / "fuel-100k.json").read_text())
            config.update(name="toy-1k", reference="reference_toy", checks=["progress"])
            text = json.dumps(config, indent=1)
        elif rel.startswith("traffic/"):
            mix = json.loads((chip / "traffic" / "dashboard.json").read_text())
            for k in mix["kinds"]:
                if k["kind"] == "platform":
                    k["kind"] = "progress"
            text = json.dumps(mix, indent=1)
        (chip / rel).write_text(text)
    bench = json.loads(bench_file.read_text())
    bench["configs"].append({
        "name": "toy-1k", "source": "made up for this test", "reduced": [],
        "file": "benchmarks/chip/configs/toy-1k.json", "why": "a deployment joining as files"})
    bench["workloads"].append({
        "name": CELL, "config": "toy-1k", "traffic": "toy-progress", "chips": 1,
        "why": "the dashboard mix with progress reads, checked by the deployment's own module"})
    bench["per_layer"].append({
        "name": "admitted_per_plane_step", "unit": "req/step", "better": "higher",
        "source": "program_counter", "layer": "gateway admission", "moves": "read_p50_ms",
        "workloads": [CELL]})
    bench_file.write_text(json.dumps(bench, indent=1))
    return before


def _rehearse(root: Path, workload: str, *args: str) -> subprocess.Popen:
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    return subprocess.Popen(
        [sys.executable, str(root / "benchmarks" / "chip" / "rehearse.py"),
         "--workload", workload, *args],
        cwd=root, env=env, stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True)


def _result(proc: subprocess.Popen) -> dict:
    try:
        out, err = proc.communicate(timeout=600)
    except subprocess.TimeoutExpired:
        proc.kill()
        proc.communicate()
        raise
    assert proc.returncode == 0, err[-4000:]
    return json.loads(out.strip().splitlines()[-1])


@pytest.fixture
def copy(tmp_path):
    """The benchmark's files, copied, with the program beside them."""
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    junk = shutil.ignore_patterns("__pycache__", ".bench_trace", ".jax_cache")
    for path in bench["paths"]:
        shutil.copytree(ROOT / path, tmp_path / path, ignore=junk)
    shutil.copy2(ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    (tmp_path / "src").symlink_to(ROOT / "src", target_is_directory=True)
    return tmp_path


def test_a_deployment_joins_as_new_files_only(copy):
    chip = copy / "benchmarks" / "chip"
    kept = _digests(chip)
    before = _join(copy)

    procs = {"plain": _rehearse(copy, CELL, "--seed", str(SEED)),
             "traced": _rehearse(copy, CELL, "--seed", str(SEED + 1), "--trace", "1"),
             "fault": _rehearse(copy, CELL, "--seed", str(SEED + 2), "--fault", "phantom_round"),
             "accepted": _rehearse(copy, ACCEPTED, "--seed", str(SEED + 3))}
    out = {name: _result(p) for name, p in procs.items()}

    plain, traced, fault, accepted = (out[k] for k in ("plain", "traced", "fault", "accepted"))
    assert plain["correct"] is True, plain["checks"]
    assert plain["checks"]["progress_miss"] == {"value": 0, "limit": 0}
    assert traced["correct"] is True, traced["checks"]
    assert set(traced["metrics"]) == {"admitted_per_plane_step"}
    assert traced["metrics"]["admitted_per_plane_step"]["value"] > 0
    assert fault["correct"] is False
    assert fault["checks"]["progress_miss"]["value"] > 0
    # the accepted cell keeps its own checks, and reads correct beside the toy
    assert accepted["correct"] is True, accepted["checks"]
    assert "progress_miss" not in accepted["checks"]

    changed = {k for k, v in _digests(chip).items() if kept.get(k, v) != v}
    gone = set(kept) - set(_digests(chip))
    assert not changed and not gone, (changed, gone)
    after = json.loads((copy / "BENCHMARK.json").read_text())
    for key, entries in before.items():
        if isinstance(entries, list) and key in ("configs", "workloads", "per_layer"):
            assert after[key][: len(entries)] == entries, key
        else:
            assert after[key] == entries, key
