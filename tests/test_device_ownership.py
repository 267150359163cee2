"""One owner of the chip, found without a probe (PR 22): a device backend
that cannot initialise raises, ``--processes`` keeps JAX out of every shard
process, the compile cache is placed from outside, and ``get_stats`` says
which path produced each merge and what device the process holds."""

import os
import subprocess
import sys

import pytest

from conftest import write_sstable_fixture
from dbeel_tpu import device
from dbeel_tpu.config import DEVICE_BACKENDS, parse_args
from dbeel_tpu.storage.compaction import (
    MERGE_PATHS,
    compaction_stats,
    get_strategy,
)
from dbeel_tpu.storage.sstable import SSTable

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _python(code_or_argv, **env):
    """A fresh interpreter on this checkout, with ``env`` on top."""
    argv = (
        ["-c", code_or_argv]
        if isinstance(code_or_argv, str)
        else list(code_or_argv)
    )
    full = {**os.environ, "PYTHONPATH": REPO, **env}
    return subprocess.run(
        [sys.executable, *argv],
        env=full,
        cwd=REPO,
        capture_output=True,
        text=True,
        timeout=90,
    )


@pytest.mark.parametrize("backend", ["auto", *DEVICE_BACKENDS])
def test_backend_raises_when_jax_cannot_initialise(backend):
    # No host merge in the device's place: with a platform JAX cannot
    # bring up, resolving the backend ends the process non-zero.
    out = _python(
        "from dbeel_tpu.storage.compaction import get_strategy\n"
        f"print(get_strategy({backend!r}).name)",
        JAX_PLATFORMS="no_such_platform",
    )
    assert out.returncode != 0, out.stdout
    assert "no_such_platform" in out.stderr
    assert out.stdout.strip() == ""


def test_node_with_device_backend_exits_nonzero_without_a_device(tmp_dir):
    out = _python(
        [
            "-m", "dbeel_tpu.server.run",
            "--dir", tmp_dir + "/db",
            "--shards", "1",
            "--compaction-backend", "device",
        ],
        JAX_PLATFORMS="no_such_platform",
    )
    assert out.returncode != 0
    assert "no_such_platform" in out.stderr
    assert not os.path.exists(tmp_dir + "/db")  # it never served


def test_processes_auto_resolves_to_native_without_importing_jax():
    out = _python(
        "import logging, sys\n"
        "logging.basicConfig(level='INFO')\n"
        "from dbeel_tpu.config import parse_args\n"
        "from dbeel_tpu.server.run import host_merge_config\n"
        "cfg = host_merge_config("
        "parse_args(['--processes', '--shards', '2']))\n"
        "print(cfg.compaction_backend, 'jax' in sys.modules)\n"
        "kept = host_merge_config(parse_args("
        "['--processes', '--compaction-backend', 'heap']))\n"
        "print(kept.compaction_backend)\n"
    )
    assert out.returncode == 0, out.stderr
    assert out.stdout.split() == ["native", "False", "heap"]
    assert out.stderr.count("auto -> native") == 1  # one line says why


@pytest.mark.parametrize("backend", DEVICE_BACKENDS)
def test_processes_refuses_an_explicit_device_backend(backend, capsys):
    with pytest.raises(SystemExit) as e:
        parse_args(["--processes", "--compaction-backend", backend])
    assert e.value.code == 2
    assert "one process" in capsys.readouterr().err
    # The single-process node is the device deployment.
    assert (
        parse_args(["--compaction-backend", backend]).compaction_backend
        == backend
    )


def test_compile_cache_honours_the_environment(monkeypatch, tmp_dir):
    import jax

    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", tmp_dir + "/cache")
    before = jax.config.jax_compilation_cache_dir
    assert device.compile_cache_dir() == tmp_dir + "/cache"
    assert device.place_compile_cache() == tmp_dir + "/cache"
    # With the variable set, nothing is set in code.
    assert jax.config.jax_compilation_cache_dir == before


def test_compile_cache_default_is_a_fixed_path_in_the_checkout(
    monkeypatch,
):
    import jax

    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    want = os.path.join(REPO, ".jax_cache")
    before = jax.config.jax_compilation_cache_dir
    try:
        assert device.compile_cache_dir() == want
        assert device.place_compile_cache() == want
        assert jax.config.jax_compilation_cache_dir == want
        assert device.compile_cache_dir() == want  # never pid/time-derived
    finally:
        jax.config.update("jax_compilation_cache_dir", before)


def test_no_other_code_places_a_compile_cache():
    # One helper: server, bench and smoke all go through device.py.
    offenders = []
    for root, dirs, files in os.walk(REPO):
        dirs[:] = [
            d
            for d in dirs
            if not d.startswith((".", "_")) and d != "tests"
        ]
        for name in files:
            if not name.endswith(".py"):
                continue
            path = os.path.join(root, name)
            with open(path, errors="replace") as f:
                if "jax_compilation_cache_dir" in f.read():
                    offenders.append(os.path.relpath(path, REPO))
    assert offenders == [os.path.join("dbeel_tpu", "device.py")]


def test_stats_name_the_device_the_process_holds(monkeypatch):
    monkeypatch.setattr(device, "_held", None)
    block = compaction_stats.stats()
    assert block["platform"] is None and block["device_kind"] is None
    assert set(block["paths"]) == set(MERGE_PATHS)
    monkeypatch.setattr(
        device,
        "_held",
        {"platform": "tpu", "device_kind": "TPU v5 lite", "count": 1},
    )
    block = compaction_stats.stats()
    assert (block["platform"], block["device_kind"]) == (
        "tpu",
        "TPU v5 lite",
    )
    assert block["device_count"] == 1
    # acquire() records what JAX reports (the tests' cpu platform).
    monkeypatch.setattr(device, "_held", None)
    held = device.acquire()
    assert held["platform"] == "cpu" and held["count"] >= 1
    assert device.held() is held


@pytest.mark.parametrize(
    "backend,path",
    [
        ("heap", "heap"),
        ("cpu", "columnar"),
        ("native", "native"),
        ("device", "single_shot"),
        ("device_full", "device_full"),
        ("distributed", "distributed"),
    ],
)
def test_each_merge_is_counted_under_the_path_that_produced_it(
    tmp_dir, backend, path
):
    os.makedirs(tmp_dir + "/t")
    for idx in (0, 2):
        write_sstable_fixture(
            tmp_dir + "/t",
            idx,
            [
                (b"k%05d" % i, b"v%d" % idx, 100 + idx)
                for i in range(idx, 400, 3)
            ],
        )
    before = compaction_stats.stats()["paths"]
    sources = [SSTable(tmp_dir + "/t", i, None) for i in (0, 2)]
    result = get_strategy(backend).merge(
        sources, tmp_dir + "/t", 1, None, False, 1 << 60
    )
    for s in sources:
        s.close()
    assert result.entry_count > 0
    after = compaction_stats.stats()["paths"]
    moved = {k: after[k] - before[k] for k in after if after[k] != before[k]}
    assert moved == {path: 1}


def test_bench_refuses_to_report_a_cpu_run():
    # No "CPU-fallback report" under the device metric's name: on a host
    # whose JAX reports the cpu the benchmark is an error, before any
    # run is built.
    out = _python(["bench.py", "--keys", "1000"], JAX_PLATFORMS="cpu")
    assert out.returncode != 0
    assert out.stdout.strip() == ""
    assert "reports the cpu" in out.stderr
