"""The readers of the program's device scopes, on a hand-made raw
trace whose answers can be worked out on paper (milliseconds below).

The traced span runs 0 -> 100. Programs on the chip:

  jit__reset   5-6     not a decode step
  jit__decode 10-30    embed 1 | attention kernel 6, a product nested
                       lm_attention/lm_mlp 1 (the OUTERMOST name files
                       it: mixer), state 2 | mlp 4, experts/router 1 |
                       an unscoped copy-done 1 | head 2, sample 0.5,
                       traps 0.5, retire 0.5; 0.5 idle -> 19.5 of 20
  jit__decode 40-60    attention 8, state 2 | mlp 6 | copy-done 2 |
                       head 1, sample 1 -> 20 of 20
  jit__chunk  62-80    a loop's event 63-79 that spans its body's two
                       attention products (5 and 6) and has 5 to itself;
                       head 1
  jit__decode 90-110   cut by the slice's edge: not counted, though
                       its first operation (90-95) starts inside
"""

import json
import os

import pytest

import run as harness
from lib import scopes

MS = 1e-3
D = "jit(_decode)/"
NAMES = ("step_mixer_ms", "step_mlp_ms", "step_head_ms",
         "step_unscoped_share")


def op(kind, start, dur, path):
    return [start * MS, dur * MS,
            "%%x.1 = f32[64]{0} %s(f32[64]{0} %%p)" % kind, path]


RAW = {
    "span": [0.0, 100 * MS],
    "programs": [[s * MS, d * MS, n] for s, d, n in (
        (5, 1, "jit__reset(3)"), (10, 20, "jit__decode(1)"),
        (40, 20, "jit__decode(1)"), (62, 18, "jit__chunk(2)"),
        (90, 20, "jit__decode(1)"))],
    "ops": [
        op("fusion", 5, 1, "jit(_reset)/scatter"),
        op("fusion", 10, 1, D + "lm_embed/gather"),
        op("custom-call", 11, 6, D + "lm_attention/paged/pallas_call"),
        op("fusion", 17, 1, D + "lm_attention/lm_mlp/dot_general"),
        op("custom-call", 18, 2, D + "lm_state/ssd/pallas_call"),
        op("fusion", 20, 4, D + "lm_mlp/dot_general"),
        op("sort", 24, 1, D + "lm_experts/router/sort"),
        op("copy-done", 25, 1, ""),
        op("fusion", 26, 2, D + "lm_head/dot_general"),
        op("fusion", 28, 0.5, D + "step_sample/argmax"),
        op("fusion", 28.5, 0.5, D + "step_traps/reduce_and"),
        op("fusion", 29, 0.5, D + "step_retire/concatenate"),
        op("custom-call", 40, 8, D + "lm_attention/paged/pallas_call"),
        op("custom-call", 48, 2, D + "lm_state/ssd/pallas_call"),
        op("fusion", 50, 6, D + "lm_mlp/dot_general"),
        op("copy-done", 56, 2, ""),
        op("fusion", 58, 1, D + "lm_head/dot_general"),
        op("fusion", 59, 1, D + "step_sample/argmax"),
        op("while", 63, 16, "jit(_chunk)/lm_attention/while"),
        op("fusion", 64, 5, "jit(_chunk)/lm_attention/while/body/dot_general"),
        op("fusion", 70, 6, "jit(_chunk)/lm_attention/while/body/dot_general"),
        op("fusion", 79, 1, "jit(_chunk)/lm_head/dot_general"),
        op("fusion", 90, 5, D + "lm_embed/gather"),
    ],
}


class Ctx(object):
    def __init__(self):
        self.lines = []

    def log(self, *a):
        self.lines.append(" ".join(str(x) for x in a))

    def trace_dir(self):
        raise AssertionError("the raw trace is in `run`: nothing to load")


def spec_of(name):
    return harness.load_json("layer_metrics", name + ".json")


def read(name, raw, ctx=None, run=None):
    spec = spec_of(name)
    reader = harness.load_module("layer_metrics/readers", spec["reader"])
    run = {"scope_raw": raw} if run is None else run
    return reader.read(object(), run, spec["args"], ctx or Ctx())


def test_the_three_sums_and_the_share_are_medians_over_whole_executions():
    # mixer 9 and 10, MLP (with the experts) 5 and 6, after the last
    # block 3.5 and 2, unscoped 1 of 19.5 and 2 of 20
    assert read("step_mixer_ms", RAW) == pytest.approx(9.5)
    assert read("step_mlp_ms", RAW) == pytest.approx(5.5)
    assert read("step_head_ms", RAW) == pytest.approx(2.75)
    assert read("step_unscoped_share", RAW) == pytest.approx(
        100 * (1 / 19.5 + 2 / 20) / 2)


def test_the_outermost_name_of_the_vocabulary_files_an_operation():
    vocab = spec_of("step_mixer_ms")["args"]["vocabulary"]
    assert scopes.outermost(D + "lm_attention/lm_mlp/dot_general",
                            vocab) == "lm_attention"
    assert scopes.outermost(D + "lm_experts/router/sort",
                            vocab) == "lm_experts"
    assert scopes.outermost("jit(_decode)/dot_general", vocab) == "unscoped"
    assert scopes.outermost("", vocab) == "unscoped"
    rows = scopes.account(RAW, r"^jit__decode\(", vocab)
    assert len(rows) == 2  # the third is cut by the slice's edge
    assert rows[0]["by_scope"]["lm_attention"] == pytest.approx(7 * MS)
    assert "lm_mlp" in rows[0]["by_scope"]
    assert rows[0]["by_scope"]["lm_mlp"] == pytest.approx(4 * MS)
    # the parts are the whole: nothing counted twice, nothing lost
    for r in rows:
        assert sum(r["by_scope"].values()) == pytest.approx(r["total"])


def test_a_loops_event_keeps_only_what_its_body_leaves_it():
    vocab = spec_of("step_mixer_ms")["args"]["vocabulary"]
    (row,) = scopes.account(RAW, r"^jit__chunk\(", vocab)
    # 16 less the two products of 5 and 6, then the products themselves
    assert row["by_scope"]["lm_attention"] == pytest.approx(16 * MS)
    assert row["total"] == pytest.approx(17 * MS)
    kinds = {k: v for (s, k), v in row["by_kind"].items()
             if s == "lm_attention"}
    assert kinds["while f32[64]"] == pytest.approx(5 * MS)


def test_the_log_is_made_once_a_run_with_both_programs_tables():
    ctx, run = Ctx(), {"scope_raw": RAW}
    for name in NAMES:
        assert read(name, RAW, ctx, run) is not None
    tables = [ln for ln in ctx.lines if "median ms an execution" in ln]
    assert len(tables) == 2  # the decode program's, the chunk's; once
    decode, chunk = tables
    assert "jit__decode" in decode and "jit__chunk" in chunk
    assert "lm_attention" in decode and "custom-call f32[64] 7.0000" in decode
    assert "unscoped" in decode and "copy-done f32[64] 1.5000" in decode
    assert "2 executions; operations 19.7500 ms of an execution's " \
           "20.0000 ms" in decode
    assert "1 executions; operations 17.0000 ms" in chunk


def test_nothing_is_read_where_nothing_matches_or_nothing_is_named():
    none = dict(RAW, programs=[p for p in RAW["programs"]
                               if "decode" not in p[2]])
    # a program that names none of its parts, as a parent commit does
    # (its scopes were `granite_mamba`, ...): every operation unscoped
    bare = dict(RAW, ops=[o[:3] + [o[3].replace("lm_", "granite_")
                                   .replace("step_", "")]
                          for o in RAW["ops"]])
    for name in NAMES:
        assert read(name, none) is None
        assert read(name, bare) is None
        # a run with no device plane (a rehearsal on a CPU)
        spec = spec_of(name)
        reader = harness.load_module("layer_metrics/readers", spec["reader"])
        assert reader.read(None, {}, spec["args"], Ctx()) is None


def test_the_metric_files_spell_the_programs_own_names():
    from paddle_tpu.models.scopes import SCOPES

    decode = spec_of("decode_step_ms")["args"]["program_match"]
    with open(os.path.join(harness.ROOT, "BENCHMARK.json")) as f:
        manifest = {m["name"]: m for m in json.load(f)["per_layer"]}
    cells = None
    named = set()
    for name in NAMES:
        args = spec_of(name)["args"]
        assert args["program_match"] == decode
        assert tuple(args["vocabulary"]) == SCOPES
        assert set(args["scopes"]) <= set(SCOPES) | {scopes.UNSCOPED}
        named |= set(args["scopes"])
        entry = manifest[name]
        assert (entry["source"], entry["layer"], entry["moves"],
                entry["better"]) == ("device_trace", "compiled steps",
                                     "itl_p95_ms", "lower")
        cells = cells or entry["workloads"]
        assert entry["workloads"] == cells and len(cells) == 5
    # every scope is some metric's but the embedding, which only the
    # account's guard counts (as scoped)
    assert named == (set(SCOPES) - {"lm_embed"}) | {scopes.UNSCOPED}


def test_load_reads_the_scope_path_off_the_events_metadata(tmp_path):
    """A trace file as the profiler writes one, made by hand: the
    scope path is the `tf_op` stat of an event's METADATA on the device
    plane; times are picoseconds from each line's own timestamp."""
    pb2 = scopes._xplane_pb2()
    space = pb2.XSpace()
    dev = space.planes.add(name="/device:TPU:0")
    dev.stat_metadata[7].name = "tf_op"
    dev.stat_metadata[8].name = "hlo_category"
    texts = {1: ("%f.1 = f32[64]{0} fusion(f32[64]{0} %p), kind=kLoop",
                 D + "lm_mlp/dot_general:"),
             2: ("%copy-done = f32[64]{0} copy-done(%copy-start)", None),
             3: ("jit__decode(9)", None)}
    for mid, (name, path) in texts.items():
        md = dev.event_metadata[mid]
        md.id, md.name = mid, name
        md.stats.add(metadata_id=8, str_value="x")
        if path:
            md.stats.add(metadata_id=7, str_value=path)
    ops = dev.lines.add(name="XLA Ops", timestamp_ns=1000)
    for mid, off, dur in ((1, 2_000_000, 3_000_000), (2, 5_000_000, 500_000),
                          (1, 50_000_000, 1_000_000)):  # past the span
        ops.events.add(metadata_id=mid, offset_ps=off, duration_ps=dur)
    mods = dev.lines.add(name="XLA Modules", timestamp_ns=1000)
    mods.events.add(metadata_id=3, offset_ps=1_500_000, duration_ps=5_000_000)
    host = space.planes.add(name="/host:CPU")
    host.event_metadata[1].name = "bench.traced"
    line = host.lines.add(name="python3", timestamp_ns=0)
    # 1 us before the device line's first tick, 21 us long
    line.events.add(metadata_id=1, offset_ps=0, duration_ps=21_000_000)
    where = tmp_path / "plugins" / "profile" / "t"
    where.mkdir(parents=True)
    (where / "h.xplane.pb").write_bytes(space.SerializeToString())

    raw = scopes.load(str(tmp_path), "bench.traced")
    us = 1e-6
    assert raw["span"] == pytest.approx([0.0, 21 * us])
    assert len(raw["ops"]) == 2
    (s, d, name, path), copy = raw["ops"]
    assert (s, d) == pytest.approx((3 * us, 3 * us))
    assert name.startswith("%f.1") and path == D + "lm_mlp/dot_general"
    assert copy[3] == "" and copy[0] == pytest.approx(6 * us)
    assert raw["programs"] == [[pytest.approx(2.5 * us),
                                pytest.approx(5 * us), "jit__decode(9)"]]
    vocab = spec_of("step_mlp_ms")["args"]["vocabulary"]
    (row,) = scopes.account(raw, r"^jit__decode\(", vocab)
    assert row["by_scope"] == {"lm_mlp": pytest.approx(3 * us),
                               "unscoped": pytest.approx(0.5 * us)}
    with pytest.raises(RuntimeError, match="no host span"):
        scopes.load(str(tmp_path), "bench.elsewhere")
