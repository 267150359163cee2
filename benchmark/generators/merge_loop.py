"""Traffic ``merge_loop``: whole merges of the deployment's runs, back to
back, until ``--seconds`` of merge wall have passed.  The rate is all
input keys over all merge wall: nothing is left out of either."""

from __future__ import annotations

import statistics

from benchmark.harness.common import Run, host_merges, say
from benchmark.harness import tracing


def warm(run: Run, job) -> None:
    """One untimed device merge: compiles, or finds the program in the
    persistent cache.  Set-up."""
    wall, n = job.merge(job.strategy)
    sha = job.take_output(True)
    seen = job.compiles.take()
    run.facts["setup_warm_merge_s"] = wall
    run.facts["setup_compile_s"] = sum(seen["compile_s"])
    say(
        f"set-up: untimed device merge {wall:.2f}s wall, backend compiles "
        f"{[round(s, 1) for s in seen['compile_s']]} s, compile cache "
        f"{seen['cache_hits']} hit(s) {seen['cache_misses']} miss(es)"
    )
    if sha != job.oracle_sha or n != job.model_entries:
        run.wrong.append("the untimed device merge differs from the oracle")


def measure(run: Run, job, _state=None) -> None:
    run.stats_before = job.counters()
    walls, shas = [], []
    traced = 0
    tracer = tracing.InProcessTracer(run) if run.trace else None
    merges_traced = int(run.traffic.get("traced_merges", 2))
    while sum(walls) < run.seconds:
        if tracer and not traced and sum(walls) >= run.seconds / 2:
            tracer.start()
        wall, n = job.merge(job.strategy)
        if tracer and tracer.on:
            traced += 1
            if traced == merges_traced:
                tracer.stop()
        # The first and the last output are compared with the oracle's.
        sha = job.take_output(
            not walls or sum(walls) + wall >= run.seconds
        )
        walls.append(wall)
        shas.append(sha)
        run.attempted += 1
        if n != job.model_entries:
            run.failed += 1
            run.wrong.append(
                f"merge {len(walls)} wrote {n} entries, the model has "
                f"{job.model_entries}"
            )
    if tracer and tracer.on:
        tracer.stop()
    run.stats_after = job.counters()
    seen = job.compiles.take()
    host = host_merges(run.stats_before, run.stats_after)
    if any(host.values()):
        run.wrong.append(f"host-path merges in the window: {host}")
    hashed = [s for s in shas if s is not None]
    if not hashed or any(s != job.oracle_sha for s in (hashed[0], hashed[-1])):
        run.wrong.append(
            "an output triplet's SHA-256 differs from the host merge's"
        )
    total_wall = sum(walls)
    run.end_to_end["merge_keys_per_s"] = job.keys_in * len(walls) / total_wall
    run.facts.update(
        merge_wall_s_median=statistics.median(walls),
        compile_s_in_window=float(sum(seen["compile_s"])),
        merges=len(walls),
    )
    say(
        f"window: {len(walls)} merges of {job.keys_in} keys, wall each "
        f"{[round(w, 3) for w in walls]} s, sum {total_wall:.2f}s; "
        f"backend compiles in the window {seen['compile_s']}, cache "
        f"{seen['cache_hits']} hit(s) {seen['cache_misses']} miss(es)"
    )
    from dbeel_tpu.storage import native

    say(f"window: odirect_fallbacks (process) {native.odirect_fallbacks()}")
