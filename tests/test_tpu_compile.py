"""The main path's kernels, compiled for a TPU v5e that is described and
not attached (no chip time): what the chip's compiler refuses — a program
that does not fit 16 GB of HBM, a shape it cannot lay out — fails here.

One file on purpose: the worker that runs it loads the TPU's compiler
library and keeps it until it exits.  The topology is described inside a
fixture, never at import.  Nothing runs, so this says nothing of results
or times.
"""

import numpy as np
import pytest

HBM_BYTES = 16 * 10**9  # one v5e chip


@pytest.fixture(scope="module")
def topo():
    import jax
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    # A compile for a described device can be written to the persistent
    # cache but never read back: keep it out.
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        return topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding

    return SingleDeviceSharding(topo.devices[0])


def _spec(shape, sharding, dtype=np.uint32):
    import jax

    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _fits(compiled, in_flight: int = 1) -> int:
    """Arguments + outputs + temporaries of one program, asserted to fit
    the chip ``in_flight`` times over (the launches the caller keeps
    queued)."""
    m = compiled.memory_analysis()
    need = (
        m.argument_size_in_bytes
        + m.output_size_in_bytes
        + m.temp_size_in_bytes
    )
    assert in_flight * need < HBM_BYTES, (
        f"{in_flight} x {need / 1e9:.2f} GB does not fit "
        f"{HBM_BYTES / 1e9:.0f} GB"
    )
    return need


@pytest.mark.parametrize(
    "k", [8, 64], ids=["major-10m", "neworder-64"]
)
def test_pipeline_exact_prefix_kernel_at_production_shape(one_chip, k):
    # (J, K, P) = (4, 8, 2^17): what ops/pipeline.py launches for the
    # 10M-key, 8-run major compaction where a partition takes the
    # exact operand; (4, 64, 2^14): what neworder-64.merge launches for
    # every one of its 58 partitions (dense ordered keys: a shift would
    # collapse neighbouring order numbers).  Two launches are kept in
    # flight.
    from dbeel_tpu.ops import bitonic, pipeline

    j, p = pipeline._LAUNCH_BATCH, pipeline.max_partition_rows(k)
    assert k * p == pipeline._MAX_KP
    compiled = bitonic.merge_runs_prefix64_packed_batch_kernel.lower(
        _spec((j, k, p, 2), one_chip),
        _spec((j, k), one_chip),
        pack_bits=bitonic.rid_pack_bits(k),
    ).compile()
    _fits(compiled, in_flight=2)


@pytest.mark.parametrize("k,p", [(2, 1 << 15), (64, 1 << 11)])
def test_pipeline_rebased_u32_kernel(one_chip, k, p):
    # The one-word operand at a served tree's pair merge and at a wide
    # merge (smaller P than production: this kernel's 2^17 compile
    # takes half a minute and the exact twin above holds the bound).
    from dbeel_tpu.ops import bitonic, pipeline

    j = pipeline._LAUNCH_BATCH
    compiled = bitonic.merge_runs_prefix32_packed_batch_kernel.lower(
        _spec((j, k, p), one_chip),
        _spec((j, k), one_chip),
        pack_bits=bitonic.rid_pack_bits(k),
    ).compile()
    _fits(compiled, in_flight=2)


@pytest.mark.parametrize(
    "k,p,ceiling_gb",
    # ~25 % above what the compiler counts for the kernel as committed
    # (PERF.md §3); the row-major network of PR 27-34 read 166.7 and
    # 106.0 GB here, 3.2 GB of temporaries each.
    [(64, 1 << 14, 24.0), (8, 1 << 17, 12.5)],
    ids=["wide-64", "major-10m"],
)
def test_cells_launch_moves_no_more_than_its_layout_needs(
    one_chip, k, p, ceiling_gb
):
    # The one-word kernel at the two shapes the benchmark's cells launch.
    # A stage that leaves a minor dimension under 128 elements is padded
    # to the (8, 128) tiling and shows here as bytes: the compiler's own
    # count of one launch, no chip needed.
    from dbeel_tpu.ops import bitonic, pipeline

    j = pipeline._LAUNCH_BATCH
    assert k * p == pipeline._MAX_KP and p == pipeline.max_partition_rows(k)
    compiled = bitonic.merge_runs_prefix32_packed_batch_kernel.lower(
        _spec((j, k, p), one_chip),
        _spec((j, k), one_chip),
        pack_bits=bitonic.rid_pack_bits(k),
    ).compile()
    _fits(compiled, in_flight=2)
    assert compiled.cost_analysis()["bytes accessed"] < ceiling_gb * 1e9
    assert compiled.memory_analysis().temp_size_in_bytes < 1e9


def test_wide_merge_never_chooses_the_refused_shape(one_chip):
    # The negative case behind _MAX_KP: the chip's compiler refuses the
    # exact-prefix kernel at (4, 64, 2^17, 2) ("Program hbm requirement
    # 36.03G"), a shape _MAX_P2 alone allowed.  _choose_partitions now
    # bounds K * P, so 64 runs of 2^17 rows each get P <= 2^14.
    from dbeel_tpu.ops import pipeline

    assert pipeline.max_partition_rows(64) == 1 << 14
    assert pipeline.max_partition_rows(8) == pipeline._MAX_P2
    rng = np.random.default_rng(3)
    runs = []
    for _ in range(64):
        prefix = np.sort(
            rng.integers(0, 1 << 63, size=1 << 17, dtype=np.uint64)
        ).astype(">u8")
        runs.append(
            pipeline._Run(None, 0, None, None, None, prefix64=prefix)
        )
    _splitters, bounds, p2 = pipeline._choose_partitions(runs)
    k2 = pipeline._pow2(len(runs))
    assert k2 * p2 <= pipeline._MAX_KP, (k2, p2)
    assert max(int(np.diff(b).max()) for b in bounds) <= p2


def test_single_shot_prefix_kernel_at_a_pair_merge(one_chip):
    # The path every merge under PIPELINE_MIN_BYTES takes; a served tree
    # (compaction_factor 2) mostly merges pairs of flushed tables.
    from dbeel_tpu.ops import bitonic

    k, p = 2, 1 << 13
    compiled = bitonic._prefix_kernel_from_runs.lower(
        tuple(_spec((p, 2), one_chip) for _ in range(k)),
        _spec((k,), one_chip),
        out_rows=k * p,
    ).compile()
    _fits(compiled)


def test_single_shot_full_column_kernel_at_a_pair_merge(one_chip):
    # Where more than 2% of rows tie on the 8-byte prefix — every
    # YCSB-style "user…" keyspace — the single-shot path re-sorts on
    # the nine-column stack.
    from dbeel_tpu.ops import bitonic

    compiled = bitonic.merge_runs_perm_kernel.lower(
        _spec((2, 1 << 14, bitonic.NUM_COLS), one_chip)
    ).compile()
    _fits(compiled)


def test_coalescer_batch_kernel(one_chip):
    # pack_jobs' common (jobs, K, P) stack for two shards' pair merges.
    from dbeel_tpu.ops import bitonic

    jobs, k, p = 2, 2, 1 << 13
    compiled = bitonic.merge_runs_prefix_batch_kernel.lower(
        _spec((jobs, k, p, 2), one_chip),
        _spec((jobs, k), one_chip),
        out_rows=k * p,
    ).compile()
    _fits(compiled)


def test_filter_mask_kernels_at_a_staged_page(one_chip):
    # The exact two-word compare over one ROW_BUCKET of a staged column.
    from dbeel_tpu.ops import query_kernels as qk

    rows = qk.ROW_BUCKET
    words = _spec((rows,), one_chip)
    flags = _spec((rows,), one_chip, np.bool_)
    word = _spec((), one_chip)
    flag = _spec((), one_chip, np.bool_)
    cmp = qk.kernels()["cmp"].lower(
        words, words, flags, flags, word, word, op=">="
    ).compile()
    _fits(cmp)
    rng = qk.kernels()["range"].lower(
        words, words, flags, flags, word, word, word, word, flag, flag
    ).compile()
    _fits(rng)


def test_distributed_sample_sort_on_the_2x2_mesh(topo):
    # One program across four chips: shard_map, all_gather, all_to_all.
    from jax.sharding import Mesh, NamedSharding, PartitionSpec

    from dbeel_tpu.parallel import dist_merge

    n_dev = len(topo.devices)
    assert n_dev == 4
    mesh = Mesh(np.array(topo.devices), ("shards",))
    m = 1 << 12  # rows per device
    capacity = int(m * 2.0 / n_dev) + dist_merge._NUM_SAMPLES
    compiled = dist_merge._dist_kernel.lower(
        _spec(
            (m * n_dev, dist_merge.NUM_COLS),
            NamedSharding(mesh, PartitionSpec("shards", None)),
        ),
        mesh=mesh,
        capacity=capacity,
        n_dev=n_dev,
    ).compile()
    _fits(compiled)
    # The exchange stays an all-to-all; the compiler turns the small
    # splitter all_gather into an all-reduce.
    text = compiled.as_text()
    assert "all-to-all" in text
    assert "all-gather" in text or "all-reduce" in text
