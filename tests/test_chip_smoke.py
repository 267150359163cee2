"""``chip_smoke.py`` rehearsed on the cpu at a tiny size: the same script
the driver runs on the chip, with its serve and major phases, its path
counters — and its refusal to pass a cpu run off as the chip."""

import json
import os
import shutil
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SMOKE = os.path.join(REPO, "chip_smoke.py")


def _smoke(args, cache_dir, cwd=REPO, script=SMOKE, devices=1):
    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    env.update(
        JAX_PLATFORMS="cpu",
        JAX_COMPILATION_CACHE_DIR=cache_dir,
        PYTHONPATH="",
    )
    if devices > 1:
        env["XLA_FLAGS"] = (
            f"--xla_force_host_platform_device_count={devices}"
        )
    out = subprocess.run(
        [sys.executable, script, *args],
        env=env,
        cwd=cwd,
        capture_output=True,
        text=True,
        timeout=100,
    )
    lines = [ln for ln in out.stdout.splitlines() if ln.strip()]
    return out, lines


def _cache_entries(cache_dir):
    return sorted(
        name for name in os.listdir(cache_dir) if name.endswith("-cache")
    )


@pytest.fixture(scope="module")
def rehearsal(tmp_path_factory):
    cache_dir = str(tmp_path_factory.mktemp("jax_cache"))
    out, lines = _smoke(["--tiny", "--rehearsal"], cache_dir)
    return out, lines, cache_dir


def test_tiny_rehearsal_runs_serve_and_major_and_counts_paths(rehearsal):
    out, lines, _cache = rehearsal
    assert out.returncode == 0, out.stdout[-3000:] + out.stderr[-2000:]
    result = json.loads(lines[-1])
    assert result["ok"] is True and result["rehearsal"] is True
    text = out.stdout
    for phase in ("== serve ==", "== major =="):
        assert phase in text
    for proof in (
        "read-back of acknowledged writes",
        "re-read after overwrite/delete",
        "is not exact in float32",
        "the node stopped cleanly",
        "ok: single_shot passes:",
        "ok: no host-merge passes:",
        "ok: no merge failed",
        "ok: filter masks evaluated on the device:",
        # What an unstamped scan met is reported, in either outcome.
        "batch-class level=",
        "default (batch-class) client: count(n >= ",
        "device output triplet SHA-256 equals native",
        "'pipeline': 2",
        "16777217.0 > 16777216.0 is True on the device lane",
    ):
        assert proof in text, proof


def test_a_cpu_run_is_never_printed_as_the_chip(rehearsal):
    out, lines, _cache = rehearsal
    result = json.loads(lines[-1])
    assert result["device"] == {"platform": "cpu", "kind": "cpu", "count": 1}
    assert '"tpu"' not in out.stdout
    assert "platform=cpu" in out.stdout  # what the node said it holds


def _cold_pass(text):
    return next(
        ln for ln in text.splitlines() if ln.strip().startswith("device cold:")
    ).split("; warm:")[0]


def test_second_run_compiles_nothing_it_compiled_before(rehearsal):
    # The major phase's shapes are fixed (the served tree's depend on
    # which tables each pass happened to group): cold there means the
    # cache, and a second process in the same checkout only hits it.
    first_out, _lines, cache_dir = rehearsal
    assert " 0 hit(s)" in _cold_pass(first_out.stdout)
    assert " 0 miss(es)" not in _cold_pass(first_out.stdout)
    first = _cache_entries(cache_dir)
    assert first, "the first run cached no program"
    out, lines = _smoke(["--tiny", "--rehearsal"], cache_dir)
    assert out.returncode == 0, out.stdout[-3000:]
    assert json.loads(lines[-1])["ok"] is True
    assert " 0 miss(es)" in _cold_pass(out.stdout)
    assert " 0 hit(s)" not in _cold_pass(out.stdout)
    assert set(first) <= set(_cache_entries(cache_dir))


def test_four_chip_option_runs_the_mesh_phase_and_nothing_else(tmp_dir):
    # On four virtual cpu devices: the distributed sample sort and the
    # pipeline's mesh= form against the single-device strategy and the
    # native oracle, and no serve or major phase.
    out, lines = _smoke(
        ["--chips", "4", "--tiny", "--rehearsal"],
        tmp_dir + "/cache",
        devices=4,
    )
    assert out.returncode == 0, out.stdout[-3000:] + out.stderr[-2000:]
    result = json.loads(lines[-1])
    assert result["ok"] is True and result["rehearsal"] is True
    assert result["device"]["count"] == 4
    text = out.stdout
    assert "== mesh ==" in text
    assert "== serve ==" not in text and "== major ==" not in text
    for proof in (
        "sharded over 4 devices",
        "each of the four devices merged rows of its own",
        "all four devices held rows of the sorted output, none lost",
        "no exchange overflow on uniform keys",
        "mesh-pipeline and distributed outputs all equal native",
    ):
        assert proof in text, proof


def test_without_rehearsal_a_cpu_host_fails(tmp_dir):
    out, lines = _smoke(["--tiny"], tmp_dir + "/cache")
    assert out.returncode != 0
    result = json.loads(lines[-1])
    assert result["ok"] is False
    assert "no accelerator" in result["error"]
    assert '"tpu"' not in out.stdout


@pytest.mark.parametrize("option", ["--docs", "--keys"])
def test_sizes_are_not_options(tmp_dir, option):
    # Sizes follow from --tiny and --chips alone (chip_smoke.sizes); a
    # cut on a slow host is the script's own printed "CUT:" line.
    out, lines = _smoke(["--tiny", option, "5"], tmp_dir + "/cache")
    assert out.returncode == 2
    assert "unrecognized arguments" in out.stderr
    assert lines == []


def test_outside_the_repository_it_prints_no_result(tmp_dir):
    alone = shutil.copy(SMOKE, tmp_dir + "/chip_smoke.py")
    out, lines = _smoke([], tmp_dir + "/cache", cwd=tmp_dir, script=alone)
    assert out.returncode != 0
    assert lines == []
