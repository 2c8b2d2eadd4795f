"""The program's scopes and feed spans, from a profile to the per-layer
numbers.  A hand-made profile checks each reader's exact value; a small
profile recorded on a v5e (``data/scoped/``) checks that they read a real
one; the parent's readers keep their values on the recorded trace."""

import json
import pathlib
import shutil
import time

import jax
import pytest

import cell
import harness
import peaks
import scoped
import trace_reduce as tr

DATA = pathlib.Path(__file__).resolve().parent / "data"
MS = 1e6  # ns
STEP, EAGER = "jit_body", "jit_convert_element_type"


def _hand():
    """Window 0..100 ms, one step, two chips.  Chip 0 runs an eager feed op
    ``copy.1`` (4..5) whose name the step also has (under F), then the step:
    a loop 12..72 holding F, send, B, W and one unscoped op, then opt.
    Chip 1 waits 30 ms in its send and 8 in the reduction after the loop."""
    spans = [
        ("bench.side_at", 0, 10 * MS), ("repro.feed", 1 * MS, 8 * MS),
        ("repro.feed.batch", 1 * MS, 2 * MS), ("repro.feed.build", 3 * MS, 3 * MS),
        ("repro.feed.put", 6 * MS, 3 * MS), ("bench.step", 10 * MS, 5 * MS),
        ("bench.block", 15 * MS, 85 * MS),
    ]
    ops0 = [
        ("copy.1", EAGER, 4, 1), ("while.1", STEP, 12, 60),
        ("fusion.1", STEP, 12, 8), ("fusion.2", STEP, 20, 5), ("fusion.3", STEP, 25, 5),
        ("collective-permute.1", STEP, 30, 10), ("fusion.4", STEP, 40, 6),
        ("fusion.5", STEP, 46, 4), ("copy.1", STEP, 50, 2), ("fusion.6", STEP, 52, 8),
        ("fusion.7", STEP, 60, 2), ("fusion.8", STEP, 64, 8),
        ("fusion.9", STEP, 75, 10), ("fusion.10", STEP, 90, 20),  # clipped at 100
    ]
    ops1 = [
        ("fusion.1", STEP, 20, 20), ("collective-permute.1", STEP, 40, 30),
        ("fusion.4", STEP, 70, 10), ("fusion.9", STEP, 80, 10),
        ("all-reduce.1", STEP, 90, 8),
    ]
    as_ns = lambda ops: [(n, m, s * MS, d * MS) for n, m, s, d in ops]
    scopes = {
        "fusion.1": "F", "fusion.2": "F/attn", "fusion.3": "F/head_ce", "copy.1": "F",
        "collective-permute.1": "send", "fusion.4": "B/attn", "fusion.5": "B/head_ce",
        "fusion.6": "W", "fusion.7": "W/head_ce", "fusion.8": "", "while.1": "",
        "fusion.9": "opt", "fusion.10": "opt", "all-reduce.1": "opt/sync",
    }
    return scoped.Scoped(
        {"/device:TPU:0": as_ns(ops0), "/device:TPU:1": as_ns(ops1)}, spans, STEP, scopes, 1
    )


# chip 0 / chip 1, ms: F 8+5+5+2 / 20, B 6+4 / 10, W 8+2 / 0, opt 10+10 /
# 10+8, head_ce 5+4+2 / 0, attn 5+6 / 10, send 10 / 30, sync 0 / 8; the
# eager copy.1 counts in no scope.  Busy less send and sync: 81-10 /
# 78-38 of 100.  Feed 1..9: chip 0 busy 4..5, chip 1 idle throughout.
# Unscoped: chip 0 the loop's own 2 ms and fusion.8's 8, chip 1 none.
EXPECT = {
    "f_ms": 20.0, "b_ms": 10.0, "w_ms": 5.0, "opt_ms": 19.0, "head_ce_ms": 5.5,
    "attn_ms": 10.5, "send_ms": 20.0, "stage_idle_share": 44.5, "feed_wait_ms": 7.5,
    "unscoped_ms": 5.0,
}


def _read(name, profile, monkeypatch, c=None, root=cell.ROOT):
    """The reader ``name`` (under the checkout ``root``) on ``profile``, as
    a traced run of the cell ``c`` would read it."""
    monkeypatch.setattr(scoped, "_take", lambda run, log: profile)
    if c is None:
        run = harness.RunInfo({}, {}, len(profile.ops), profile.steps, 0.1, 1, None, tr.Trace({}, []))
    else:
        run = harness.RunInfo(
            c.config, c.traffic, c.chips, profile.steps, 0.1, c.tokens_per_step,
            peaks.PEAKS["TPU v5 lite"], tr.Trace({}, []), c.arch,
        )
    return harness.load_reader(name, root)(run)


@pytest.mark.parametrize("name", sorted(EXPECT))
def test_reader_on_hand_made_profile(name, monkeypatch):
    assert _read(name, _hand(), monkeypatch) == pytest.approx(EXPECT[name])


def test_per_chip_values_and_log():
    s = _hand()
    assert scoped.scope_seconds(s, "F") == pytest.approx({"/device:TPU:0": 0.020, "/device:TPU:1": 0.020})
    assert scoped.stage_idle(s) == pytest.approx({"/device:TPU:0": 0.29, "/device:TPU:1": 0.60})
    assert scoped.feed_wait(s) == pytest.approx({"/device:TPU:0": 0.007, "/device:TPU:1": 0.008})
    # unscoped: the loop's own 2 ms (60 less its ops' 58) and fusion.8, of 81 busy
    share, top = scoped.unscoped(s, "/device:TPU:0")
    assert share == pytest.approx(10 / 81)
    assert top == [["fusion.8", pytest.approx(8.0)], ["while.1", pytest.approx(2.0)]]
    gaps = scoped.named_gaps(s)
    assert [name for name, _ in gaps] == [
        "bench.step", "bench.side_at>repro.feed>repro.feed.put", "bench.block",
        "bench.side_at>repro.feed>repro.feed.batch", "bench.block",
    ]
    assert [g for _, g in gaps] == pytest.approx([0.020, 0.007, 0.005, 0.004, 0.003])
    lines = scoped.report(s)
    assert len(lines) == 4 and "stage idle 29.000%" in lines[1]


def test_nothing_to_read():
    s = _hand()
    s.ops = {p: [op for op in evs if "collective-permute" not in op[0]] for p, evs in s.ops.items()}
    assert scoped.scope_ms(s, "send") is None and scoped.stage_idle_share(s) is None
    s.spans = [sp for sp in s.spans if not sp[0].startswith("repro.")]
    assert scoped.feed_wait_ms(s) is None


def test_profile_absent_reads_nothing(monkeypatch):
    monkeypatch.setattr(scoped, "_take", lambda run, log: None)
    run = harness.RunInfo({}, {}, 1, 1, 0.1, 1, None, tr.Trace({}, []))
    assert all(harness.load_reader(name)(run) is None for name in EXPECT)
    untraced = harness.RunInfo({}, {}, 1, 1, 0.1, 1, None, None)
    assert scoped.profile(untraced) is None


def test_scope_paths_from_hlo_text():
    text = "\n".join([
        "HloModule jit_body, is_scheduled=true",
        "ENTRY %main {",
        '  %p.1 = f32[2] parameter(0), metadata={op_name="stacked[0][\'blocks\'][0][\'ln\']"}',
        '  %fusion.3 = f32[2] fusion(%p.1), metadata={op_name="jit(body)/while/body/closed_call/B/transpose(jvp(attn))/bqhd,bkhd->bhqk/dot_general" stack_frame_id=2}',
        '  ROOT %fusion.4 = f32[2] fusion(%fusion.3), metadata={op_name="jit(body)/opt/cond/branch_1_fun/mul"}',
        "  %copy.2 = f32[2] copy(%fusion.4)",
        "}",
    ])
    module, scopes = scoped.hlo_scopes(text)
    assert module == "jit_body"
    assert scopes == {"p.1": "", "fusion.3": "B/attn", "fusion.4": "opt"}
    assert scoped.scope_path("jit(body)/F/head_ce/jvp(attn)/x") == "F/head_ce/attn"
    assert scoped.in_scope("B/attn", "attn") and not scoped.in_scope("B/attn", "at")


def test_extract_keeps_program_spans(tmp_path):
    """On the CPU a profile has no device plane; the host spans of the
    program and the benchmark are kept, nothing else is."""
    f = jax.jit(lambda x: x * 2)
    jax.block_until_ready(f(1.0))
    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation("bench.side_at"):
        with jax.profiler.TraceAnnotation("repro.feed"):
            time.sleep(0.001)
        with jax.profiler.TraceAnnotation("other.span"):
            pass
    jax.block_until_ready(f(2.0))
    jax.profiler.stop_trace()
    text = f.lower(1.0).compile().as_text()
    s = scoped.extract(tr.find_xplane(str(tmp_path)), text, 1)
    assert s.ops == {}
    assert [n for n, _, _ in s.spans] == ["bench.side_at", "repro.feed"]
    assert s.step_module.startswith("jit_")


def test_parent_readers_pinned_on_recorded_trace():
    """The window's own readers read the recorded trace as they did before
    the scopes came."""
    t = tr.load(str(DATA / "internlm2_1c_s4096.json"))
    run = harness.RunInfo({}, {}, 1, 1, 0.409, 16384, None, t)
    assert harness.load_reader("device_idle_share")(run) == pytest.approx(3.0587040652618636, rel=1e-12)
    assert harness.load_reader("host_data_ms")(run) == pytest.approx(4.08917, rel=1e-12)
    assert [sec for _, sec in tr.idle_gaps(t)] == pytest.approx(
        [0.004499568, 0.002607684, 0.001270336, 0.001146773, 0.000981092,
         0.000294659, 0.000293762, 0.000293424, 0.000293366, 0.000293224], rel=1e-9
    )


@pytest.mark.parametrize("path", sorted((DATA / "scoped").glob("*.json")), ids=lambda p: p.stem)
def test_recorded_profile(path, monkeypatch):
    """A few steps of a cell profiled on a v5e: every reader gives the
    value it gave there, and the passes cover the step's busy time."""
    d = json.load(open(path))
    s = scoped.Scoped.from_json(d)
    c = cell.load(d["workload"]) if "workload" in d else None
    for name, value in d["expect"].items():
        got = _read(name, s, monkeypatch, c)
        assert (got is None and value is None) or got == pytest.approx(value, rel=1e-9), name
    t0, t1 = scoped.window(s)
    busy = [
        sum(e - b for b, e in tr.busy_intervals([(n, t, dur) for n, _, t, dur in evs], t0, t1))
        for evs in s.ops.values()
    ]
    busy_ms = sum(busy) / len(busy) / s.steps / 1e6
    passes = sum(d["expect"][k] for k in ("f_ms", "b_ms", "w_ms", "opt_ms"))
    assert passes <= busy_ms * (1 + 1e-9)


# --------------------------------------------------------------------- #
# the open scope set
# --------------------------------------------------------------------- #
# One step on one chip, window 0..100 ms.  The Pallas call's attributes run
# over three lines, as a call given ``metadata=`` prints them.
PROBE_HLO = "\n".join([
    "HloModule jit_body, is_scheduled=true",
    "ENTRY %main {",
    '  %fusion.1 = bf16[8] fusion(), metadata={op_name="jit(body)/while/body/F/probe/dot_general"}',
    '  %fusion.2 = bf16[8] fusion(%fusion.1), metadata={op_name="jit(body)/while/body/probe/mul"}',
    '  %flash_dq.1 = bf16[8] custom-call(%fusion.2), custom_call_target="tpu_custom_call", '
    "frontend_attributes={kernel_metadata={",
    '"a":"1"',
    '}}, metadata={op_name="jit(body)/while/body/B/transpose(jvp(attn))/flash/pallas_call" stack_frame_id=3}',
    '  ROOT %copy.3 = bf16[8] copy(%flash_dq.1), metadata={op_name="jit(body)/while/body/copy"}',
    "}",
])
PROBE_OPS = [("fusion.1", 10, 10), ("fusion.2", 20, 5), ("flash_dq.1", 30, 8), ("copy.3", 40, 6)]
PROBE_READER = """import scoped

SCOPES = ("probe",)


def read(run):
    return scoped.reading(run, lambda s: scoped.scope_ms(s, "probe"))
"""


def _probe_profile(root):
    module, scopes = scoped.hlo_scopes(PROBE_HLO, root)
    ops = {"/device:TPU:0": [(n, module, t * MS, d * MS) for n, t, d in PROBE_OPS]}
    return scoped.Scoped(ops, [("bench.step", 0, 100 * MS)], module, scopes, 1)


def test_new_scope_is_a_new_metric_file(tmp_path, monkeypatch):
    """A reader that states a scope outside the program's set reads it from a
    copy of the benchmark's files with only that file added."""
    here = tmp_path / cell.HERE.relative_to(cell.ROOT)
    shutil.copytree(cell.HERE, here, ignore=shutil.ignore_patterns("__pycache__", "tests"))
    (here / "metrics" / "probe_ms.py").write_text(PROBE_READER)
    assert "probe" not in scoped.PROGRAM_SCOPES and "probe" not in scoped.known_scopes()
    assert scoped.known_scopes(tmp_path) == scoped.known_scopes() + ("probe",)

    before, after = _probe_profile(cell.ROOT), _probe_profile(tmp_path)
    assert before.scopes == {"fusion.1": "F", "fusion.2": "", "flash_dq.1": "B/attn/flash", "copy.3": ""}
    assert after.scopes == dict(before.scopes, **{"fusion.1": "F/probe", "fusion.2": "probe"})
    assert scoped.scope_ms(before, "probe") is None
    assert _read("probe_ms", after, monkeypatch, root=tmp_path) == pytest.approx(15.0)
    # what the new scope names leaves the unscoped time; the rest reads alike
    assert _read("unscoped_ms", before, monkeypatch) == pytest.approx(11.0)
    assert _read("unscoped_ms", after, monkeypatch, root=tmp_path) == pytest.approx(6.0)
    for s in (before, after):
        assert [scoped.scope_ms(s, sc) for sc in ("F", "B", "attn", "flash")] == pytest.approx([10.0, 8.0, 8.0, 8.0])


@pytest.mark.parametrize("stated", ["probe", ("probe", "a/b"), ("",), (1,)])
def test_scopes_stated_badly_are_refused(stated, tmp_path):
    metrics = cell.metrics_dir(tmp_path)
    metrics.mkdir(parents=True)
    (metrics / "bad.py").write_text(f"SCOPES = {stated!r}\n")
    with pytest.raises(ValueError, match="SCOPES"):
        scoped.known_scopes(tmp_path)


@pytest.mark.parametrize("op_name, path", [
    ("jit(body)/while/body/F/attn/flash/_attend/pallas_call", "F/attn/flash"),
    ("jit(body)/while/body/B/transpose(jvp(attn))/flash/pallas_call", "B/attn/flash"),
    ("jit(body)/while/body/B/transpose(jvp(attn))/transpose", "B/attn"),
    ("jit(body)/shard_map/while/body/closed_call/copy", ""),
])
def test_flash_is_a_scope_under_attn(op_name, path):
    assert "flash" in scoped.known_scopes()  # stated by metrics/attn_roofline.py
    assert scoped.scope_path(op_name) == path
    assert scoped.in_scope(path, "attn") == ("attn" in op_name)


def test_hlo_scopes_reads_an_instruction_over_several_lines():
    module, scopes = scoped.hlo_scopes(PROBE_HLO)
    assert module == "jit_body"
    assert scopes == {"fusion.1": "F", "fusion.2": "", "flash_dq.1": "B/attn/flash", "copy.3": ""}
    one_line = PROBE_HLO.replace('{\n"a":"1"\n}}', '{"a":"1"}}')
    assert one_line.count("\n") == PROBE_HLO.count("\n") - 2
    assert scoped.hlo_scopes(one_line) == (module, scopes)


# --------------------------------------------------------------------- #
# attention's roofline share
# --------------------------------------------------------------------- #
# Per token and layer, 16 query heads and 8 key/value heads of 128, bf16:
# 3 * seq * 16 * (128 + 128) FLOPs; forward bytes 2 * (q 2048 + k 1024 +
# v 1024 + o 2048) + lse 4 * 16 = 12352, backward 2 * (q, k, v, o, dO,
# dq, dk, dv: 12288) + lse 64 = 24640.  A chip's layers see 16384 tokens
# at seq 4096 (2 layers, one chip), 12288 at seq 1024 (2 of 8 layers a chip).
ATTN_WORK = {
    "internlm2_1c_s4096": (3 * 4096 * 16 * 256 * 16384 * 2, (12352 + 24640) * 16384 * 2),
    "internlm2_4c_zbv_s1024": (3 * 1024 * 16 * 256 * 12288 * 2, (12352 + 24640) * 12288 * 2),
}


@pytest.mark.parametrize("name", sorted(ATTN_WORK))
def test_attn_roofline_against_hand_counts(name, monkeypatch):
    c = cell.load(name)
    reader = cell.load_module(cell.metrics_dir() / "attn_roofline.py", "attn_roofline")
    run = harness.RunInfo(c.config, c.traffic, c.chips, 1, 0.1, c.tokens_per_step, None, None, c.arch)
    flops, nbytes = ATTN_WORK[name]
    assert reader.work(run) == (flops, nbytes)
    # compute bounds both: at seq 4096 1.649 TFLOP, 8.372 ms, against 1.480 ms of HBM
    roof = flops / 197e12
    assert roof > nbytes / 819e9
    # the hand-made profile's attn is 10.5 ms a step, mean over chips
    assert _read("attn_roofline", _hand(), monkeypatch, c) == pytest.approx(100 * roof / 10.5e-3, rel=1e-12)

