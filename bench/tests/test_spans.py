"""The readers of the program's own spans and scopes on a small synthetic
trace: three recorded rounds, a segment dispatched before the trace
started, a one-step phase with no loop of its own, and an op XLA put in
with no metadata; and the op names read from a small ``.xplane.pb``."""

import pytest

from benchlib import spans
from benchlib.spans import Op, Span

SEG = "jit(seg)/serve_segment"
MS = 1e6                                     # ns


def op(name, op_name, start, dur):
    return Op(name, op_name, start * MS, dur * MS)


def phase(which, start, dur):
    return op(f"%while.{start} = (s32[]) while((s32[]) %t), body=%b",
              f"{SEG}/{which}/while", start, dur)


def body(scope, start, dur, which="decode_phase"):
    return op(f"%fusion.{start} = bf16[16,3072] fusion(%p)",
              f"{SEG}/{which}/while/body/closed_call/{scope}/dot_general",
              start, dur)


def copy(start, dur):
    return op(f"%copy.{start} = s8[513,32,128,96] copy(%p)", "", start, dur)


def span(name, start, dur, **args):
    return Span(name, start * MS, dur * MS, args)


def round_spans(start, wait, seg, step, mixed):
    """A round of 10 ms of host work around ``wait`` ms of waiting."""
    return [span("serve.round", start, 10 + wait, segment=seg, step=step),
            span("serve.schedule", start, 2),
            span("serve.pool", start + 2, 2),
            span("serve.dispatch", start + 4, 1, segment=seg, step=step,
                 mixed=mixed, steps=16),
            span("serve.wait", start + 5, wait),
            span("serve.readback", start + 5 + wait, 5)]


def tiny():
    ops = [
        # dispatched before the trace began: its round is not recorded
        phase("decode_phase", 0, 90), body("attn_kernel", 10, 50),
        # round 0 (100-510 ms): 4 mixed steps, then 12 decode steps
        phase("mixed_phase", 110, 160), body("mlp", 120, 100, "mixed_phase"),
        phase("decode_phase", 270, 230), body("attn_kernel", 280, 120),
        copy(400, 60), body("layer_carry", 460, 30),
        # round 1 (600-910 ms): one mixed step (no loop), 15 decode steps
        body("attn_kernel", 605, 10, "mixed_phase"),
        phase("decode_phase", 620, 285), body("mlp", 630, 250),
        # round 2 (1000-1410 ms): all 16 steps mixed
        phase("mixed_phase", 1010, 395), body("attn_kernel", 1020, 300),
        copy(1330, 70),
        # a pool program between rounds
        op("%scatter.1 = s32[16] scatter(%p)",
           "jit(preempt_rows)/pool/scatter", 1420, 2)]
    host = (round_spans(100, 400, 0, 32, 4) + round_spans(600, 300, 1, 48, 1)
            + round_spans(1000, 400, 2, 64, 16)
            # an idle round: no segment dispatched
            + [span("serve.round", 1500, 3, segment=3, step=80),
               span("serve.schedule", 1500, 2)])
    return spans.from_events(ops, host)


def test_rounds_hold_their_children_in_order():
    rounds = tiny().rounds()
    assert [r.span.args["segment"] for r in rounds] == [0, 1, 2]
    assert [c.name for c in rounds[0].children] == [
        "serve.schedule", "serve.pool", "serve.dispatch", "serve.wait",
        "serve.readback"]
    assert rounds[1].child("serve.dispatch").args["mixed"] == 1


def test_decode_step_counts_whole_rounds_only():
    # rounds 0 and 1: 230 + 285 ms over 12 + 15 steps; the loop of the
    # segment dispatched before the trace does not count
    assert spans.phase_step_ms(tiny(), "decode_phase") \
        == pytest.approx((230 + 285) / 27)


def test_mixed_step_skips_a_phase_without_its_loop():
    # round 1's single mixed step left no loop, so its step is not counted
    assert spans.phase_step_ms(tiny(), "mixed_phase") \
        == pytest.approx((160 + 395) / 20)


def test_unscoped_share_is_the_ops_without_a_program_scope():
    # busy: 0-90, 110-500, 605-615, 620-905, 1010-1405, 1420-1422 ms
    busy = 90 + 390 + 10 + 285 + 395 + 2
    assert spans.unscoped_share(tiny()) == pytest.approx(100 * 130 / busy)


def test_host_round_time_is_the_round_less_its_wait():
    assert spans.host_round_ms(tiny()) == pytest.approx(10.0)


def test_a_program_without_names_reads_nothing():
    bare = spans.from_events(
        [Op(o.name, "", o.start_ns, o.dur_ns) for o in tiny().ops], [])
    assert spans.unscoped_share(bare) is None
    assert spans.phase_step_ms(bare, "decode_phase") is None
    assert spans.host_round_ms(bare) is None
    assert spans.phase_step_ms(None, "mixed_phase") is None


def _pb(*fields) -> bytes:
    """A protobuf message of ``(number, value)`` fields: ints as varints,
    bytes and strings length-delimited."""
    def varint(n):
        out = b""
        while True:
            out += bytes([n & 0x7F | (0x80 if n > 0x7F else 0)])
            n >>= 7
            if not n:
                return out
    out = b""
    for num, val in fields:
        if isinstance(val, int):
            out += varint(num << 3) + varint(val)
        else:
            val = val.encode() if isinstance(val, str) else val
            out += varint(num << 3 | 2) + varint(len(val)) + val
    return out


def _plane(name, stat_names, events):
    """An ``XPlane`` with its stat names ``{id: name}`` and event metadata
    ``[(id, name, display_name, [XStat fields])]``."""
    fields = [(2, name)]
    fields += [(5, _pb((1, k), (2, _pb((1, k), (2, v)))))
               for k, v in stat_names.items()]
    fields += [(4, _pb((1, i), (2, _pb((1, i), (2, n), (4, shown),
                                       *[(5, _pb(*st)) for st in stats]))))
               for i, n, shown, stats in events]
    return _pb(*fields)


def test_op_names_from_event_metadata_and_the_hlo_module(tmp_path):
    mlp = f"{SEG}/decode_phase/while/body/closed_call/mlp/dot_general"
    loop = f"{SEG}/decode_phase/while"
    hlo = _pb((1, _pb((3, _pb(                  # module, computation
        (2, _pb((1, "while.80"), (7, _pb((1, "while"), (2, loop))))),
        (2, _pb((1, "copy.9"))))))))
    stat_names = {1: "tf_op", 2: "program_id", 3: mlp + ":"}
    device = _plane("/device:TPU:0", stat_names, [
        (1, "%fusion.3 = bf16[16] fusion(%p)", "fusion.3",
         [[(1, 1), (5, mlp + ":")], [(1, 2), (3, 77)]]),
        (2, "%fusion.4 = bf16[16] fusion(%p)", "fusion.4",
         [[(1, 1), (7, 3)], [(1, 2), (3, 77)]]),
        (3, "%while.80 = (s32[]) while(%t)", "while.80", [[(1, 2), (3, 77)]]),
        (4, "%copy.9 = s8[4] copy(%p)", "copy.9", [[(1, 2), (3, 77)]])])
    meta = _plane("/host:metadata", {1: "Hlo Proto"},
                  [(77, "jit_seg(77)", "", [[(1, 1), (6, hlo)]])])
    path = tmp_path / "t.xplane.pb"
    path.write_bytes(_pb((1, meta), (1, device)))
    assert spans.op_names(path) == {
        "%fusion.3 = bf16[16] fusion(%p)": mlp,
        "%fusion.4 = bf16[16] fusion(%p)": mlp,
        "%while.80 = (s32[]) while(%t)": loop,
        "%copy.9 = s8[4] copy(%p)": ""}
