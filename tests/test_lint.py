"""dbeel-lint self-tests: the tree is clean, and every rule still
FIRES — each checker gets a known-good/known-bad fixture pair, plus
full-copy regression fixtures proving that seeding a cross-plane
drift (verb mismatch, trailer-size change, arity change) makes the
parity checker exit nonzero.  A lint suite nobody proves can fail is
the same trap as the silently-skipping native tests tier1.sh closed.
"""

import os
import shutil
import subprocess
import sys
import textwrap

sys.path.insert(
    0, os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
)

from analysis import (  # noqa: E402
    error_taxonomy,
    lint as lint_mod,
    stats_schema,
    wire_parity,
    yield_hazards,
)
from analysis.common import Repo, strip_c_comments  # noqa: E402

REPO_ROOT = os.path.dirname(
    os.path.dirname(os.path.abspath(__file__))
)

# Everything the wire-parity + taxonomy checkers read; fixture trees
# copy these so a single seeded edit is the only difference from the
# real (clean) tree.
_PARITY_FILES = [
    "dbeel_tpu/cluster/messages.py",
    "dbeel_tpu/errors.py",
    "dbeel_tpu/query.py",
    "dbeel_tpu/server/shard.py",
    "dbeel_tpu/server/db_server.py",
    "dbeel_tpu/server/dataplane.py",
    "dbeel_tpu/server/metrics.py",
    "dbeel_tpu/server/scan.py",
    "dbeel_tpu/server/watch.py",
    "dbeel_tpu/client/__init__.py",
    "native/src/dbeel_native.cpp",
    "native/src/dbeel_client.cpp",
]


def _copy_fixture(tmp_path):
    root = str(tmp_path / "tree")
    for rel in _PARITY_FILES:
        dst = os.path.join(root, rel)
        os.makedirs(os.path.dirname(dst), exist_ok=True)
        shutil.copyfile(os.path.join(REPO_ROOT, rel), dst)
    return root


def _edit(root, rel, old, new, count=0):
    path = os.path.join(root, rel)
    with open(path) as f:
        src = f.read()
    assert old in src, f"fixture edit anchor missing: {old!r}"
    src = src.replace(old, new) if count == 0 else src.replace(
        old, new, count
    )
    with open(path, "w") as f:
        f.write(src)


# ---------------------------------------------------------------------
# The real tree is clean, and the CLI agrees.
# ---------------------------------------------------------------------


def test_tree_is_clean():
    findings = lint_mod.run(REPO_ROOT)
    assert findings == [], "\n".join(f.render() for f in findings)


def test_cli_exits_zero_on_tree_and_knows_its_rules():
    proc = subprocess.run(
        [sys.executable, "-m", "analysis.lint"],
        cwd=REPO_ROOT,
        capture_output=True,
        text=True,
        timeout=120,
    )
    assert proc.returncode == 0, proc.stdout + proc.stderr
    listing = subprocess.run(
        [sys.executable, "-m", "analysis.lint", "--list-rules"],
        cwd=REPO_ROOT,
        capture_output=True,
        text=True,
        timeout=60,
    )
    assert listing.returncode == 0
    for rule in ("wire-parity", "yield-hazards", "stats-schema",
                 "error-taxonomy"):
        assert rule in listing.stdout


# ---------------------------------------------------------------------
# Wire parity: seeded cross-plane drift must fail.
# ---------------------------------------------------------------------


def test_parity_clean_on_unmodified_copy(tmp_path):
    root = _copy_fixture(tmp_path)
    assert wire_parity.check(Repo(root)) == []


def test_parity_flags_c_verb_mismatch(tmp_path):
    # The regression the ISSUE names: a verb drifts between
    # messages.py and a C source -> nonzero.
    root = _copy_fixture(tmp_path)
    _edit(
        root,
        "native/src/dbeel_native.cpp",
        '"get_digest"',
        '"get_digset"',
    )
    findings = wire_parity.check(Repo(root))
    assert any("get_digset" in f.message for f in findings), findings


def test_parity_flags_python_only_verb(tmp_path):
    # A verb added to the registry without encoder/handler/response.
    root = _copy_fixture(tmp_path)
    _edit(
        root,
        "dbeel_tpu/cluster/messages.py",
        '    REARM = "rearm"\n',
        '    REARM = "rearm"\n    TRUNCATE = "truncate"\n',
        count=1,
    )
    findings = wire_parity.check(Repo(root))
    msgs = "\n".join(f.message for f in findings)
    assert "truncate" in msgs and "no encoder" in msgs, findings
    assert "not handled in handle_shard_request" in msgs


def test_parity_flags_trailer_size_drift(tmp_path):
    # The exact 17-vs-25B stale-ABI class PR 6 guarded at runtime.
    root = _copy_fixture(tmp_path)
    _edit(
        root,
        "native/src/dbeel_native.cpp",
        "constexpr uint32_t kCoordGetTrailerHdr = 25;",
        "constexpr uint32_t kCoordGetTrailerHdr = 17;",
    )
    findings = wire_parity.check(Repo(root))
    assert any(
        "trailer header size drift" in f.message for f in findings
    ), findings


def test_parity_flags_arity_drift(tmp_path):
    root = _copy_fixture(tmp_path)
    _edit(
        root,
        "native/src/dbeel_native.cpp",
        "k_set ? 6u : k_del ? 5u : 4u",
        "k_set ? 6u : k_del ? 6u : 4u",
    )
    findings = wire_parity.check(Repo(root))
    assert any("arity drift" in f.message for f in findings), findings


def test_parity_flags_trace_index_drift(tmp_path):
    # Tracing plane (PR 9): the trace id rides exactly one slot past
    # the deadline on every data verb — a seeded Python-side table
    # drift must fail the lint.
    root = _copy_fixture(tmp_path)
    _edit(
        root,
        "dbeel_tpu/server/shard.py",
        "        ShardRequest.GET: 5,\n"
        "        ShardRequest.GET_DIGEST: 5,\n"
        "        ShardRequest.MULTI_SET: 5,\n"
        "        ShardRequest.MULTI_GET: 5,\n",
        "        ShardRequest.GET: 6,\n"
        "        ShardRequest.GET_DIGEST: 5,\n"
        "        ShardRequest.MULTI_SET: 5,\n"
        "        ShardRequest.MULTI_GET: 5,\n",
    )
    findings = wire_parity.check(Repo(root))
    assert any(
        "trace-field arity drift" in f.message for f in findings
    ), findings


def test_parity_flags_trace_dialect_drift_in_c(tmp_path):
    # The C parser must recognize the want+2 trace dialect (and punt
    # it); seeding it to want+3 is wire drift.
    root = _copy_fixture(tmp_path)
    _edit(
        root,
        "native/src/dbeel_native.cpp",
        "const bool has_trace = nelem == want + 2u;",
        "const bool has_trace = nelem == want + 3u;",
    )
    findings = wire_parity.check(Repo(root))
    assert any(
        "trace-field arity drift" in f.message
        or "trace-dialect" in f.message
        for f in findings
    ), findings


def test_parity_flags_scan_arity_drift(tmp_path):
    # Scan plane (PR 12): the SCAN peer frame's fixed arity is pinned
    # between the encoder and shard.py's handler constant.
    root = _copy_fixture(tmp_path)
    _edit(
        root,
        "dbeel_tpu/server/shard.py",
        "_SCAN_PEER_ARITY = 12",
        "_SCAN_PEER_ARITY = 9",
    )
    findings = wire_parity.check(Repo(root))
    assert any(
        "scan peer-frame arity drift" in f.message for f in findings
    ), findings


def test_parity_flags_scan_verb_lost_in_c_client(tmp_path):
    # Scan plane (PR 12): the C client must keep emitting both scan
    # op tokens — losing one strands the compiled fleet scanless.
    root = _copy_fixture(tmp_path)
    _edit(
        root,
        "native/src/dbeel_client.cpp",
        '"scan_next"',
        '"scan_nxt"',
    )
    findings = wire_parity.check(Repo(root))
    msgs = "\n".join(f.message for f in findings)
    assert "no longer emits the 'scan_next' op" in msgs, findings
    # ...and the typo'd token itself is unknown-wire-string drift.
    assert "scan_nxt" in msgs


def test_parity_flags_scan_arity_drift_in_c_shard_plane(tmp_path):
    # Query compute plane (PR 13): the THIRD copy of the scan
    # peer-frame arity — the C shard plane's punt recognition —
    # must move with the other two.
    root = _copy_fixture(tmp_path)
    _edit(
        root,
        "native/src/dbeel_native.cpp",
        "constexpr uint32_t kScanPeerArity = 12;",
        "constexpr uint32_t kScanPeerArity = 10;",
    )
    findings = wire_parity.check(Repo(root))
    assert any(
        "scan peer-frame arity drift" in f.message
        and "kScanPeerArity" in f.message
        for f in findings
    ), findings


def test_parity_flags_membership_tail_drift(tmp_path):
    # Elastic membership: the optional NodeMetadata token-list tail is
    # pinned by NODE_WIRE_TAIL_SLOTS vs the encoder's append count —
    # seeding the constant is drift.
    root = _copy_fixture(tmp_path)
    _edit(
        root,
        "dbeel_tpu/cluster/messages.py",
        "NODE_WIRE_TAIL_SLOTS = 1",
        "NODE_WIRE_TAIL_SLOTS = 2",
    )
    findings = wire_parity.check(Repo(root))
    assert any(
        "membership tail drift" in f.message for f in findings
    ), findings


def test_parity_flags_vnode_token_slot_drift_in_c(tmp_path):
    # The C client parses ring tokens at kNodeTokensSlot, which must
    # equal NodeMetadata.to_wire's base tuple length — a drifted index
    # would shatter the ring for C-routed traffic on a vnode cluster.
    root = _copy_fixture(tmp_path)
    _edit(
        root,
        "native/src/dbeel_client.cpp",
        "constexpr uint32_t kNodeTokensSlot = 6;",
        "constexpr uint32_t kNodeTokensSlot = 7;",
    )
    findings = wire_parity.check(Repo(root))
    assert any(
        "vnode dialect drift" in f.message for f in findings
    ), findings


def test_parity_flags_dropped_epoch_fence_read(tmp_path):
    # db_server dropping the 'epoch' request read silently disables
    # the migration write fence server-side.
    root = _copy_fixture(tmp_path)
    _edit(
        root,
        "dbeel_tpu/server/db_server.py",
        'epoch = request.get("epoch")',
        'epoch = request.get("deadline_ms")',
    )
    findings = wire_parity.check(Repo(root))
    assert any(
        "no longer reads the 'epoch'" in f.message for f in findings
    ), findings


def test_parity_flags_dropped_epoch_stamp_in_client(tmp_path):
    # The Python client not stamping 'epoch' on writes leaves stale-
    # ring writes unfenced during migration.
    root = _copy_fixture(tmp_path)
    _edit(
        root,
        "dbeel_tpu/client/__init__.py",
        'request["epoch"] = self._cluster_epoch',
        "pass",
    )
    findings = wire_parity.check(Repo(root))
    assert any(
        "no longer stamps the 'epoch'" in f.message for f in findings
    ), findings


def test_parity_flags_qos_index_drift(tmp_path):
    # QoS plane (ISSUE 14): the class element rides exactly one slot
    # past the trace id on every data verb — a seeded Python-side
    # table drift must fail the lint.
    root = _copy_fixture(tmp_path)
    _edit(
        root,
        "dbeel_tpu/server/shard.py",
        "    _PEER_QOS_INDEX = {\n"
        "        ShardRequest.SET: 8,",
        "    _PEER_QOS_INDEX = {\n"
        "        ShardRequest.SET: 9,",
    )
    findings = wire_parity.check(Repo(root))
    assert any(
        "qos-field arity drift" in f.message for f in findings
    ), findings


def test_parity_flags_qos_dialect_drift_in_c(tmp_path):
    # The C shard parser must recognize the want+3 qos dialect;
    # seeding it to want+4 is wire drift.
    root = _copy_fixture(tmp_path)
    _edit(
        root,
        "native/src/dbeel_native.cpp",
        "const bool has_qos = nelem == want + 3u;",
        "const bool has_qos = nelem == want + 4u;",
    )
    findings = wire_parity.check(Repo(root))
    assert any(
        "qos-field arity drift" in f.message
        or "qos-dialect" in f.message
        for f in findings
    ), findings


def test_parity_flags_qos_trace_punt_lost_in_c(tmp_path):
    # Inside the qos dialect a LIVE trace id must punt to Python
    # (sampled frames own the span piggyback) — removing the punt is
    # drift.
    root = _copy_fixture(tmp_path)
    _edit(
        root,
        "native/src/dbeel_native.cpp",
        "if (trace_v > 0) return -1;",
        "if (trace_v > 1) return -1;",
    )
    findings = wire_parity.check(Repo(root))
    assert any(
        "qos dialect must punt" in f.message for f in findings
    ), findings


def test_parity_flags_tenant_field_lost_in_c_plane(tmp_path):
    # The C data plane must keep recognizing (and punting) the
    # "tenant" request field — losing the token would serve quota'd
    # traffic unmetered.
    root = _copy_fixture(tmp_path)
    _edit(
        root,
        "native/src/dbeel_native.cpp",
        'slice_eq(ks, kn, "tenant")',
        'slice_eq(ks, kn, "tennant")',
    )
    findings = wire_parity.check(Repo(root))
    msgs = "\n".join(f.message for f in findings)
    assert "no longer recognizes the 'tenant'" in msgs, findings


def test_parity_flags_spec_version_drift(tmp_path):
    # Query compute plane (PR 13): the filter/aggregate spec version
    # is pinned three ways — Python packer, coordinator parser, C
    # client pass-through validation.  Seed a one-sided bump.
    root = _copy_fixture(tmp_path)
    _edit(
        root,
        "native/src/dbeel_client.cpp",
        'constexpr char kSpecVersion[] = "q1";',
        'constexpr char kSpecVersion[] = "q2";',
    )
    findings = wire_parity.check(Repo(root))
    assert any(
        "spec version drift" in f.message for f in findings
    ), findings
    # ...and deleting one of the pins is itself a finding.
    root2 = _copy_fixture(tmp_path / "b")
    _edit(
        root2,
        "dbeel_tpu/server/scan.py",
        'SPEC_WIRE_VERSION = "q1"',
        '_SPEC_WIRE_VER_GONE = "q1"',
    )
    findings2 = wire_parity.check(Repo(root2))
    assert any(
        "spec version constant missing" in f.message
        for f in findings2
    ), findings2


def test_parity_flags_cursor_arity_drift(tmp_path):
    # Query compute plane (PR 13): encode_cursor's packed field
    # count must match the pinned _CURSOR_ARITY (what decode_cursor
    # accepts) — a one-sided cursor field would strand every
    # in-flight scan on resume.
    root = _copy_fixture(tmp_path)
    _edit(
        root,
        "dbeel_tpu/server/scan.py",
        "_CURSOR_ARITY = 10",
        "_CURSOR_ARITY = 9",
    )
    findings = wire_parity.check(Repo(root))
    assert any(
        "scan-cursor arity drift" in f.message for f in findings
    ), findings


def test_parity_flags_watch_feed_arity_drift(tmp_path):
    # Watch/CDC plane (ISSUE 20): the WATCH_FEED peer frame's fixed
    # arity is pinned between the encoder and shard.py's handler
    # constant.
    root = _copy_fixture(tmp_path)
    _edit(
        root,
        "dbeel_tpu/server/shard.py",
        "_WATCH_PEER_ARITY = 10",
        "_WATCH_PEER_ARITY = 8",
    )
    findings = wire_parity.check(Repo(root))
    assert any(
        "watch_feed peer-frame arity drift" in f.message
        for f in findings
    ), findings


def test_parity_flags_watch_cursor_arity_drift(tmp_path):
    # Watch/CDC plane (ISSUE 20): encode_cursor's packed field count
    # must match the pinned _CURSOR_ARITY (what decode_cursor
    # accepts) — a one-sided cursor field would strand every live
    # subscription on its next poll.
    root = _copy_fixture(tmp_path)
    _edit(
        root,
        "dbeel_tpu/server/watch.py",
        "_CURSOR_ARITY = 6",
        "_CURSOR_ARITY = 5",
    )
    findings = wire_parity.check(Repo(root))
    assert any(
        "watch-cursor arity drift" in f.message for f in findings
    ), findings


def test_parity_flags_watch_cursor_version_lost_in_client(tmp_path):
    # Watch/CDC plane (ISSUE 20): the Python client's read-only
    # cursor peek recognizes the server's version token — if it
    # drifts, the Watcher monotonicity audit passes vacuously.
    root = _copy_fixture(tmp_path)
    _edit(
        root,
        "dbeel_tpu/client/__init__.py",
        '!= "w1"',
        '!= "w0"',
    )
    findings = wire_parity.check(Repo(root))
    assert any(
        "watch-cursor version drift" in f.message for f in findings
    ), findings


def test_parity_flags_spec_field_lost_in_c_client(tmp_path):
    root = _copy_fixture(tmp_path)
    _edit(
        root,
        "native/src/dbeel_client.cpp",
        'm.str("spec");',
        'm.str("sp_ec");',
    )
    findings = wire_parity.check(Repo(root))
    msgs = "\n".join(f.message for f in findings)
    assert "no longer emits the 'spec' request field" in msgs, (
        findings
    )


def test_parity_flags_status_byte_drift(tmp_path):
    root = _copy_fixture(tmp_path)
    _edit(
        root,
        "native/src/dbeel_client.cpp",
        "constexpr uint8_t kResponseOk = 1;",
        "constexpr uint8_t kResponseOk = 2;",
    )
    findings = wire_parity.check(Repo(root))
    assert any(
        "status-byte drift" in f.message for f in findings
    ), findings


def test_parity_clean_again_on_fresh_copy_with_ddl_tail(tmp_path):
    # The ISSUE-17 DDL tail (quotas-then-index) parses clean on an
    # unmodified copy — the three new pins all agree on the real tree.
    root = _copy_fixture(tmp_path)
    assert wire_parity.check(Repo(root)) == []


def test_parity_flags_ddl_tail_append_drift(tmp_path):
    # Seeded drift: the peer-request encoder loses its index append
    # while DDL_TAIL_SLOTS still promises two optional slots — a
    # declared index would silently never reach peers.
    root = _copy_fixture(tmp_path)
    _edit(
        root,
        "dbeel_tpu/cluster/messages.py",
        "        if index:\n            frame.append(list(index))\n",
        "",
        count=1,
    )
    findings = wire_parity.check(Repo(root))
    msgs = "\n".join(f.message for f in findings)
    assert "DDL tail drift" in msgs and "appends 1" in msgs, findings


def test_parity_flags_ddl_handler_slot_drift(tmp_path):
    # Seeded drift: the peer CREATE_COLLECTION handler stops reading
    # the index slot (request[5]) the encoder emits — the index DDL
    # would apply on the coordinator but vanish on every peer.
    root = _copy_fixture(tmp_path)
    _edit(
        root,
        "dbeel_tpu/server/shard.py",
        "request[5] if len(request) > 5 else None",
        "None",
        count=1,
    )
    findings = wire_parity.check(Repo(root))
    msgs = "\n".join(f.message for f in findings)
    assert "never reads request[5]" in msgs, findings


def test_parity_flags_ddl_gossip_slot_drift(tmp_path):
    # Same class of drift on the gossip plane: event[4] is the index
    # tail of GossipEvent.CREATE_COLLECTION.
    root = _copy_fixture(tmp_path)
    _edit(
        root,
        "dbeel_tpu/server/shard.py",
        "event[4] if len(event) > 4 else None",
        "None",
        count=1,
    )
    findings = wire_parity.check(Repo(root))
    msgs = "\n".join(f.message for f in findings)
    assert "never reads event[4]" in msgs, findings


# ---------------------------------------------------------------------
# Yield-point hazards: known-good / known-bad snippets.
# ---------------------------------------------------------------------


def _src(body: str) -> str:
    return textwrap.dedent(body)


def test_async_blocking_flags_sleep_and_sync_io():
    findings = yield_hazards.check_source(
        _src(
            """
            import time, os

            async def handler():
                time.sleep(1)
                with open("/tmp/x", "w") as f:
                    f.write("x")
                os.fsync(3)
            """
        ),
        "fixture.py",
    )
    rules = [f.rule for f in findings]
    assert rules.count("async-blocking") == 3, findings


def test_async_blocking_clean_cases():
    findings = yield_hazards.check_source(
        _src(
            """
            import asyncio, time

            def sync_path():
                time.sleep(1)  # sync context: fine

            async def handler(loop):
                await asyncio.sleep(0.1)  # yields: fine

                def journal():  # executor target: off-loop
                    with open("/tmp/x", "w") as f:
                        f.write("x")

                await loop.run_in_executor(None, journal)
                await loop.run_in_executor(
                    None, lambda: open("/tmp/y")
                )
            """
        ),
        "fixture.py",
    )
    assert findings == [], findings


def test_async_blocking_escape_comment():
    findings = yield_hazards.check_source(
        _src(
            """
            import time

            async def handler():
                time.sleep(1)  # lint: allow(async-blocking)
            """
        ),
        "fixture.py",
    )
    assert findings == [], findings


def test_stale_write_guard_flags_prefix_apply_if_newer():
    # The PRE-FIX form of apply_if_newer (ADVICE r5 low #2): probe,
    # then insert WITHOUT a stale-abort guard — the capacity wait in
    # the insert can span a flush swap and shadow a newer flushed
    # value.  The checker must flag it so the class cannot return.
    findings = yield_hazards.check_source(
        _src(
            """
            class Shard:
                @staticmethod
                async def apply_if_newer(tree, key, value, ts):
                    local = await tree.get_entry(key)
                    if local is not None and local[1] >= ts:
                        return False
                    await tree.set_with_timestamp(key, value, ts)
                    return True
            """
        ),
        "fixture.py",
    )
    assert [f.rule for f in findings] == ["stale-write-guard"], findings


def test_stale_write_guard_accepts_fixed_form():
    findings = yield_hazards.check_source(
        _src(
            """
            class Shard:
                @staticmethod
                async def apply_if_newer(tree, key, value, ts):
                    while True:
                        local = await tree.get_entry(key)
                        if local is not None and local[1] >= ts:
                            return False
                        watermark = tree.max_flushed_ts
                        if await tree.set_with_timestamp(
                            key, value, ts,
                            stale_abort_from=watermark,
                        ):
                            return True
            """
        ),
        "fixture.py",
    )
    assert findings == [], findings


def test_stale_write_guard_flags_unguarded_batch():
    findings = yield_hazards.check_source(
        _src(
            """
            async def write(col, entries):
                await col.tree.set_batch_with_timestamp(entries)
            """
        ),
        "fixture.py",
    )
    assert [f.rule for f in findings] == ["stale-write-guard"], findings


def test_real_tree_yield_rules_fire_via_checker():
    # Sanity that the in-tree audited escapes are what keeps the
    # real server clean: stripping the allow comments must surface
    # findings again (the escapes are load-bearing, not decorative).
    path = os.path.join(REPO_ROOT, "dbeel_tpu/server/shard.py")
    with open(path) as f:
        src = f.read()
    stripped = src.replace("lint: allow(async-blocking)", "")
    findings = yield_hazards.check_source(stripped, "shard.py")
    assert any(f.rule == "async-blocking" for f in findings)


# ---------------------------------------------------------------------
# Stats-schema drift: minimal synthetic tree.
# ---------------------------------------------------------------------


def _stats_tree(tmp_path, server_source: str) -> str:
    root = str(tmp_path / "stats")
    os.makedirs(os.path.join(root, "dbeel_tpu/server"))
    os.makedirs(os.path.join(root, "dbeel_tpu/client"))
    os.makedirs(os.path.join(root, "native/src"))
    with open(
        os.path.join(root, "dbeel_tpu/server/plane.py"), "w"
    ) as f:
        f.write(server_source)
    with open(
        os.path.join(root, "dbeel_tpu/client/__init__.py"), "w"
    ) as f:
        f.write("async def get_stats(self):\n    return {}\n")
    with open(
        os.path.join(root, "native/src/dbeel_client.cpp"), "w"
    ) as f:
        f.write("int64_t dbeel_cli_get_stats(void* h) { return 0; }\n")
    return root


def test_stats_schema_flags_unexported_counter(tmp_path):
    root = _stats_tree(
        tmp_path,
        _src(
            """
            class Plane:
                def work(self):
                    self.orphan_counter += 1
            """
        ),
    )
    findings = stats_schema.check(Repo(root))
    assert any(
        "orphan_counter" in f.message for f in findings
    ), findings


def test_stats_schema_accepts_exported_counter(tmp_path):
    root = _stats_tree(
        tmp_path,
        _src(
            """
            class Plane:
                def work(self):
                    self.visible_counter += 1

                def stats(self):
                    return {"visible_counter": self.visible_counter}
            """
        ),
    )
    assert stats_schema.check(Repo(root)) == []


def test_stats_schema_cross_class_name_collision_still_caught(
    tmp_path,
):
    # Another CLASS's snapshot reading its OWN same-named attribute
    # must not vacuously excuse this class's unexported counter
    # (per-class scoping of self-reads; review finding, PR 7).
    root = _stats_tree(
        tmp_path,
        _src(
            """
            class Histogram:
                def snapshot(self):
                    return {"mean": self.total / self.n}

            class Governor:
                def work(self):
                    self.total += 1
            """
        ),
    )
    findings = stats_schema.check(Repo(root))
    assert any("total" in f.message for f in findings), findings


def test_stats_schema_dotted_cross_object_export_accepted(tmp_path):
    root = _stats_tree(
        tmp_path,
        _src(
            """
            class HintLog:
                def record(self):
                    self.recorded += 1

            class Shard:
                def get_stats(self):
                    return {"hr": self.hint_log.recorded}
            """
        ),
    )
    assert stats_schema.check(Repo(root)) == []


def test_stats_schema_covers_secondary_index_plane(tmp_path):
    # ISSUE 17: secondary_index.py's IndexStats counters are
    # increment-checked like compaction.py's — a counter bumped there
    # but dropped from the get_stats.index schema must fire.
    root = _stats_tree(tmp_path, "class Unused:\n    pass\n")
    os.makedirs(os.path.join(root, "dbeel_tpu/storage"))
    with open(
        os.path.join(root, "dbeel_tpu/storage/secondary_index.py"),
        "w",
    ) as f:
        f.write(
            _src(
                """
                class IndexStats:
                    def note_quarantine(self):
                        self.runs_quarantined += 1

                    def stats(self):
                        return {}
                """
            )
        )
    findings = stats_schema.check(Repo(root))
    assert any(
        "runs_quarantined" in f.message for f in findings
    ), findings


def test_stats_schema_real_index_counters_exported():
    # The real tree's IndexStats block exports every counter it bumps
    # (the clean-tree assertion test_tree_is_clean covers this too,
    # but pin the plane explicitly so a schema regression names it).
    findings = [
        f
        for f in stats_schema.check(Repo(REPO_ROOT))
        if "secondary_index" in f.path
    ]
    assert findings == [], findings


def test_stats_schema_covers_the_pipeline_shape_block(tmp_path):
    # ISSUE 28: the real compaction.py's ``shape`` counters (launches,
    # partitions, rows, runs, tie entries of a pipeline merge) are
    # increment-checked: clean as they are, and a tree whose
    # get_stats.compaction drops the block fires.
    with open(
        os.path.join(REPO_ROOT, "dbeel_tpu/storage/compaction.py")
    ) as f:
        real = f.read()
    export = '"shape": dict(self.shape),'
    assert real.count(export) == 1
    for source, fires in ((real, False), (real.replace(export, ""), True)):
        root = _stats_tree(
            tmp_path / str(fires), "class Unused:\n    pass\n"
        )
        os.makedirs(os.path.join(root, "dbeel_tpu/storage"))
        with open(
            os.path.join(root, "dbeel_tpu/storage/compaction.py"), "w"
        ) as f:
            f.write(source)
        findings = stats_schema.check(Repo(root))
        assert any("self.shape" in f.message for f in findings) == fires


def test_pipeline_probes_nothing_and_its_library_exports_what_it_calls():
    # ISSUE 30: the library the device pipeline sees is always this
    # tree's (native.require() builds it or raises), so the pipeline
    # neither probes it for a symbol nor reads an environment knob; and
    # every symbol it calls — the list is taken from its source — is
    # one the built library exports.
    import ctypes
    import re

    from dbeel_tpu.storage import native

    with open(os.path.join(REPO_ROOT, "dbeel_tpu/ops/pipeline.py")) as f:
        source = f.read()
    for banned in ("hasattr(lib", "os.environ", "getenv"):
        assert banned not in source, banned
    called = set(re.findall(r"lib\.(dbeel_\w+)", source))
    assert {
        "dbeel_pipe_decode", "dbeel_pipe_resolve_ties", "dbeel_writer_close2",
    } <= called
    lib = native.require()
    assert [name for name in sorted(called) if not hasattr(lib, name)] == []
    # ISSUE 34: the decode's tie pass is bound as the rest of the
    # pipeline's symbols are, with its argument types and no probe.
    with open(os.path.join(REPO_ROOT, "dbeel_tpu/storage/native.py")) as f:
        binding = f.read()
    assert "lib.dbeel_pipe_resolve_ties.argtypes" in binding
    assert 'hasattr(lib, "dbeel_pipe_resolve_ties")' not in binding
    assert lib.dbeel_pipe_resolve_ties.restype is ctypes.c_int64


def test_stats_schema_escape_comment(tmp_path):
    root = _stats_tree(
        tmp_path,
        _src(
            """
            class Plane:
                def work(self):
                    # lint: allow(stats-schema)
                    self.internal_state += 1
            """
        ),
    )
    assert stats_schema.check(Repo(root)) == []


# ---------------------------------------------------------------------
# Prometheus name-flattening drift (telemetry plane, PR 11).
# ---------------------------------------------------------------------


def _prom_tree(tmp_path, server_source: str) -> str:
    """A _stats_tree plus the REAL telemetry.py, so the flattening
    check executes the real prom_name over the seeded schema keys."""
    root = _stats_tree(tmp_path, server_source)
    shutil.copyfile(
        os.path.join(REPO_ROOT, "dbeel_tpu/server/telemetry.py"),
        os.path.join(root, "dbeel_tpu/server/telemetry.py"),
    )
    return root


def test_prom_flattening_clean_on_disjoint_keys(tmp_path):
    root = _prom_tree(
        tmp_path,
        _src(
            """
            class Plane:
                def stats(self):
                    return {"ops_total": 1, "sheds_total": 2}
            """
        ),
    )
    assert stats_schema.check(Repo(root)) == []


def test_prom_flattening_flags_name_collision(tmp_path):
    # Two DISTINCT schema keys sanitizing to one metric token would
    # silently merge two series on /metrics.
    root = _prom_tree(
        tmp_path,
        _src(
            """
            class Plane:
                def stats(self):
                    return {"loop_lag.ms": 1, "loop_lag_ms": 2}
            """
        ),
    )
    findings = stats_schema.check(Repo(root))
    assert any(
        "collision" in f.message and "loop_lag" in f.message
        for f in findings
    ), findings


def test_prom_flattening_flags_lost_map(tmp_path):
    # telemetry.py without prom_name means the /metrics naming is no
    # longer lint-checked at all — that itself is drift.
    root = _prom_tree(
        tmp_path,
        _src(
            """
            class Plane:
                def stats(self):
                    return {"ok": 1}
            """
        ),
    )
    path = os.path.join(root, "dbeel_tpu/server/telemetry.py")
    with open(path) as f:
        src = f.read()
    assert "def prom_name" in src
    with open(path, "w") as f:
        f.write(src.replace("def prom_name", "def prom_name_gone"))
    findings = stats_schema.check(Repo(root))
    assert any(
        "prom_name" in f.message for f in findings
    ), findings


def test_prom_flattening_real_tree_keys_are_injective():
    # The real tree's full schema-key namespace must flatten cleanly
    # (this is what the CI lint gate enforces; pinned here so a local
    # edit sees the failure as a named test, not just a lint exit).
    findings = [
        f
        for f in stats_schema.check(Repo(REPO_ROOT))
        if "Prometheus" in f.message or "flatten" in f.message
    ]
    assert findings == [], findings


# ---------------------------------------------------------------------
# Error taxonomy: seeded unknown kind / lost special case.
# ---------------------------------------------------------------------


def test_taxonomy_clean_on_unmodified_copy(tmp_path):
    root = _copy_fixture(tmp_path)
    assert error_taxonomy.check(Repo(root)) == []


def test_taxonomy_flags_unregistered_c_kind(tmp_path):
    root = _copy_fixture(tmp_path)
    _edit(
        root,
        "native/src/dbeel_client.cpp",
        'if (kind == "KeyNotFound") {',
        'if (kind == "KeyNotFoundd") {',
        count=1,
    )
    findings = error_taxonomy.check(Repo(root))
    msgs = "\n".join(f.message for f in findings)
    assert "KeyNotFoundd" in msgs, findings


def test_taxonomy_flags_lost_overloaded_special_case(tmp_path):
    root = _copy_fixture(tmp_path)
    _edit(
        root,
        "native/src/dbeel_client.cpp",
        '"Overloaded"',
        '"Internal"',
    )
    findings = error_taxonomy.check(Repo(root))
    assert any(
        "Overloaded" in f.message and "special case" in f.message
        for f in findings
    ), findings


# ---------------------------------------------------------------------
# Infrastructure details the checkers lean on.
# ---------------------------------------------------------------------


def test_strip_c_comments_preserves_strings_and_lines():
    src = '// x "not a string"\nint a; /* multi\nline */ char* s = "a//b";\n'
    out = strip_c_comments(src)
    assert out.count("\n") == src.count("\n")
    assert '"a//b"' in out
    assert "not a string" not in out


# ---------------------------------------------------------------------
# Wire parity: atomic plane (ISSUE 19) drift seeds.
# ---------------------------------------------------------------------


def test_parity_flags_cas_punt_lost_in_native(tmp_path):
    # A native fast path that absorbs conditional writes bypasses the
    # epoch fence, the decider lock and the boot barrier at once.
    root = _copy_fixture(tmp_path)
    _edit(
        root,
        "native/src/dbeel_native.cpp",
        'slice_eq(type_s, type_n, "atomic_batch");',
        'slice_eq(type_s, type_n, "atomic_batches");',
    )
    findings = wire_parity.check(Repo(root))
    assert any(
        "punt" in f.message and "cas" in f.message for f in findings
    ), findings


def test_parity_flags_cas_verb_lost_in_server(tmp_path):
    root = _copy_fixture(tmp_path)
    _edit(
        root,
        "dbeel_tpu/server/db_server.py",
        'if rtype == "cas":',
        'if rtype == "caz":',
    )
    # The sheddable-op registry ALSO names the verb and would keep
    # the harvest satisfied on its own.
    _edit(
        root,
        "dbeel_tpu/server/db_server.py",
        '        "cas",\n',
        '        "caz",\n',
    )
    findings = wire_parity.check(Repo(root))
    assert any(
        "'cas'" in f.message and "server entry" in f.message
        for f in findings
    ), findings


def test_parity_flags_cas_verb_lost_in_python_client(tmp_path):
    root = _copy_fixture(tmp_path)
    _edit(
        root,
        "dbeel_tpu/client/__init__.py",
        '"type": "cas",',
        '"type": "caz",',
    )
    findings = wire_parity.check(Repo(root))
    assert any(
        "'cas'" in f.message and "Python client" in f.message
        for f in findings
    ), findings


def test_parity_flags_cas_verb_lost_in_c_client(tmp_path):
    root = _copy_fixture(tmp_path)
    _edit(
        root,
        "native/src/dbeel_client.cpp",
        'common_fields(&m, "cas", collection, true);',
        'common_fields(&m, "set", collection, true);',
    )
    findings = wire_parity.check(Repo(root))
    assert any(
        "C client" in f.message and "'cas'" in f.message
        for f in findings
    ), findings


def test_parity_flags_cas_expect_field_lost_in_server(tmp_path):
    # Dropping an expectation read turns a conditional write into an
    # unconditional one — the worst possible silent failure here.
    root = _copy_fixture(tmp_path)
    _edit(
        root,
        "dbeel_tpu/server/db_server.py",
        'request.get("expect_ts")',
        'request.get("expectedts")',
    )
    findings = wire_parity.check(Repo(root))
    assert any(
        "expect_ts" in f.message and "unconditionally" in f.message
        for f in findings
    ), findings


def test_parity_flags_cas_epoch_stamp_lost_in_client(tmp_path):
    root = _copy_fixture(tmp_path)
    _edit(
        root,
        "dbeel_tpu/client/__init__.py",
        '_EPOCH_STAMPED_OPS = ("set", "delete", "cas", '
        '"atomic_batch")',
        '_EPOCH_STAMPED_OPS = ("set", "delete")',
    )
    findings = wire_parity.check(Repo(root))
    assert any(
        "_EPOCH_STAMPED_OPS" in f.message for f in findings
    ), findings
