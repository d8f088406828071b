"""Device time by scope on a hand-built trace.

``data/scoped.xplane.pb`` is an XSpace written by :func:`build` below,
field by field after ``tsl/profiler/protobuf/xplane.proto``, with what a
v5e trace was found to carry (PR 24's probe call): an operation's name
stack as the ``tf_op`` string stat of its event *metadata*, its module as
the ``program_id`` stat, resolved by the names on the ``XLA Modules``
line. The first test pins the file to the builder.
"""

import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(os.path.dirname(HERE)))

from benchmark import scopes, xplane  # noqa: E402
from benchmark.tests.test_xplane import US, _bytes, _int, _str  # noqa: E402

TRACE = os.path.join(HERE, "data", "scoped.xplane.pb")
DECODE, TRAIN, SPLIT = 77, 78, 79
MODULES = {DECODE: "jit_decode_paged", TRAIN: "jit_train_step",
           SPLIT: "jit__threefry_split"}
BLOCK = "jit(decode_paged)/GPT2/block_0/attn/"
# (instruction, opcode, name stack or None, module, start_us, duration_us)
DEVICE_0 = [
    ("copy.4", "copy", None, DECODE, 0, 100),  # the compiler's own: no name
    ("fusion.1", "fusion", BLOCK + "kv_write/scatter:", DECODE, 100, 200),
    ("reshape.9", "reshape",
     BLOCK + "jit(_paged_decode_call)/kv_gather/reshape:", DECODE, 300, 50),
    ("paged_decode_attn.3", "custom-call",
     BLOCK + "jit(_paged_decode_call)/paged_decode_attn/pallas_call:",
     DECODE, 350, 200),
    ("fusion.2", "fusion", "jit(decode_paged)/GPT2/block_0/mlp/out/dot_general:",
     DECODE, 550, 150),
    # 650-800: 50 us under fusion.2, so busy is less than the sum by scope.
    ("fusion.7", "fusion",
     "jit(train_step)/shard_map/transpose(jvp(loss))/GPT2/lm_head/dot_general:",
     TRAIN, 650, 150),
    ("fusion.8", "fusion", "jit(_threefry_split)/threefry2x32:", SPLIT, 900, 50),
]
DEVICE_1 = [
    ("all-reduce.1", "all-reduce",
     "jit(train_step)/shard_map/opt_update/grad_sync/reduce_scatter:", TRAIN, 0, 400),
    ("fusion.3", "fusion", "jit(train_step)/shard_map/opt_update/mul:", TRAIN, 400, 100),
]
MARK_US = 20
TF_OP, PROGRAM_ID = 1, 2  # stat metadata ids


def _hlo(instr, opcode):
    return f"%{instr} = f32[8,128]{{1,0:T(8,128)}} {opcode}(f32[8,128]{{1,0}} %p.1)"


def _device(pid, name, ops) -> bytes:
    stat_meta = b"".join(
        _bytes(5, _int(1, sid) + _bytes(2, _int(1, sid) + _str(2, sname)))
        for sid, sname in ((TF_OP, "tf_op"), (PROGRAM_ID, "program_id")))
    metas, events = b"", b""
    for i, (instr, opcode, stack, module, start, dur) in enumerate(ops, 1):
        stats = _bytes(5, _int(1, PROGRAM_ID) + _int(3, module))
        if stack is not None:
            stats += _bytes(5, _int(1, TF_OP) + _str(5, stack))
        metas += _bytes(4, _int(1, i) + _bytes(
            2, _int(1, i) + _str(2, _hlo(instr, opcode)) + stats))
        events += _bytes(4, _int(1, i) + _int(2, start * US) + _int(3, dur * US))
    mod_metas, mod_events = b"", b""
    for j, (pid_, mname) in enumerate(sorted(MODULES.items()), 100):
        mod_metas += _bytes(4, _int(1, j) + _bytes(
            2, _int(1, j) + _str(2, f"{mname}({pid_})")))
        mod_events += _bytes(4, _int(1, j) + _int(2, 0) + _int(3, 1000 * US))
    lines = (_bytes(3, _int(1, 1) + _str(2, scopes.MODULES_LINE) + _int(3, 0)
                    + mod_events)
             + _bytes(3, _int(1, 2) + _str(2, xplane.OPS_LINE) + _int(3, 0)
                      + events))
    return _bytes(1, _int(1, pid) + _str(2, name) + lines + metas + mod_metas
                  + stat_meta)


def build() -> bytes:
    host = _bytes(1, _int(1, 9) + _str(2, "/host:CPU") + _bytes(
        3, _int(1, 1) + _str(2, "python3") + _int(3, 0)
        + _bytes(4, _int(1, 1) + _int(2, MARK_US * US) + _int(3, 1 * US)))
        + _bytes(4, _int(1, 1) + _bytes(2, _int(1, 1) + _str(2, xplane.MARK))))
    return (_device(1, "/device:TPU:0", DEVICE_0)
            + _device(2, "/device:TPU:1", DEVICE_1) + host)


def test_recorded_file_is_what_the_builder_writes():
    with open(TRACE, "rb") as f:
        assert f.read() == build()


@pytest.mark.parametrize("stack,scope", [
    (BLOCK + "kv_write/scatter:", "kv_write"),
    (BLOCK + "proj/dot_general:", "attn"),
    ("jit(train_step)/shard_map/transpose(jvp(loss))/GPT2/ln_f/mul:", "loss"),
    ("jit(train_step)/shard_map/jvp(loss)/GPT2/block_1/mlp/fc/dot_general:", "mlp"),
    ("jit(train_step)/shard_map/opt_update/zero1_gather/all_gather:", "zero1_gather"),
    ("jit(_threefry_split)/threefry2x32:", None),
    ("jit(train_step)/loss_fn/attn_mask/mul:", None),  # whole words only
    ("", None),
])
def test_an_operation_belongs_to_its_innermost_scope(stack, scope):
    assert scopes.scope_of(stack) == scope


def test_what_the_loader_reads():
    trace = scopes.load(TRACE)
    assert trace["mark_s"] == pytest.approx(MARK_US * 1e-6)
    assert [len(d) for d in trace["devices"]] == [7, 2]
    start, end, stack, module, instr = trace["devices"][0][3]
    assert (start, end) == pytest.approx((350e-6, 550e-6))
    assert stack.endswith("paged_decode_attn/pallas_call:")
    assert (module, instr) == ("jit_decode_paged", "paged_decode_attn")
    assert trace["devices"][0][0][2:] == ("", "jit_decode_paged", "copy")
    # The same planes, lines and times as the reader of busy and idle sees.
    seen = xplane.load(TRACE)
    assert seen.mark_s == pytest.approx(trace["mark_s"])
    assert [t for o in seen.devices[0].ops for t in (o[1], o[1] + o[2])] == (
        pytest.approx([t for o in trace["devices"][0] for t in o[:2]]))


def test_seconds_by_scope_over_a_window():
    us = 1e-6
    r = scopes.reduce(scopes.load(TRACE), 40 * us, 1000 * us)
    by = {k: v / us for k, v in r["by_scope"].items()}
    # Means over the two devices; the copy is clipped to 40-100.
    assert by == pytest.approx({
        "unscoped": (60 + 50) / 2, "kv_write": 200 / 2, "kv_gather": 50 / 2,
        "attn": 200 / 2, "mlp": 150 / 2, "lm_head": 150 / 2,
        "grad_sync": 360 / 2, "opt_update": 100 / 2})
    # fusion.7 lies under `loss` and, further in, under `lm_head`: innermost
    # wins, as `grad_sync` does inside `opt_update`.
    assert "loss" not in by
    # Device 0: 40-800 and 900-950; device 1: 40-500.
    assert r["busy_s"] / us == pytest.approx((810 + 460) / 2)
    assert sum(by.values()) - r["busy_s"] / us == pytest.approx(50 / 2)
    assert r["scoped"] is True
    assert r["outside"] == [
        ["jit_decode_paged:copy", pytest.approx(30 * us)],
        ["jit__threefry_split:fusion", pytest.approx(25 * us)]]


def _ctx(**over):
    said = []
    ctx = {"rehearse": False, "trace_dir": TRACE,
           "say": lambda kind, **f: said.append((kind, f)),
           # The host's clock reads 7.0 s at the mark, 20 us into the trace.
           "run": {"trace_mark": 7.0, "trace_t0": 7.0 + 30e-6,
                   "trace_t1": 7.0 + 980e-6, "steps": 2}}
    ctx.update(over)
    return ctx, said


def test_the_readers_tie_the_trace_to_the_hosts_clock():
    from benchmark.readers import scope_step_ms, scope_time_pct

    ctx, said = _ctx()
    busy = (800 + 450) / 2
    assert scope_time_pct.read(ctx, ["kv_write", "kv_gather"]) == pytest.approx(
        100 * (100 + 25) / busy)
    assert scope_time_pct.read(ctx, ["unscoped"]) == pytest.approx(100 * 50 / busy)
    assert scope_step_ms.read(ctx, ["grad_sync", "zero1_gather"]) == pytest.approx(
        1e3 * 175e-6 / 2)
    assert [kind for kind, _ in said] == ["device_time_by_scope"]  # loaded once


def test_nothing_to_read_gives_none(tmp_path):
    from benchmark.readers import scope_step_ms, scope_time_pct

    for ctx, _ in (_ctx(rehearse=True),
                   _ctx(run={"trace_mark": None, "trace_t0": 0, "trace_t1": 1})):
        assert scope_time_pct.read(ctx, ["kv_write"]) is None
        assert scope_step_ms.read(ctx, ["opt_update"]) is None
    # A program without the scope names (the parent of PR 24): operations
    # with name stacks, none of them a program scope.
    bare = tmp_path / "bare.xplane.pb"
    bare.write_bytes(build().replace(b"/kv_write/", b"/kv_xxxxx/").replace(
        b"/kv_gather/", b"/kv_yyyyyy/").replace(b"/attn/", b"/aaaa/").replace(
        b"/mlp/", b"/mmm/").replace(b"/grad_sync/", b"/gggg_gggg/").replace(
        b"(loss)", b"(llll)").replace(b"/opt_update/", b"/ooo_oooooo/").replace(
        b"/lm_head/", b"/lm_hhhh/"))
    ctx, said = _ctx(trace_dir=str(bare))
    assert scope_time_pct.read(ctx, ["unscoped"]) is None
    assert said[0][1]["by_scope"] == {"unscoped": pytest.approx(625e-6)}


if __name__ == "__main__":
    with open(TRACE, "wb") as f:
        f.write(build())
