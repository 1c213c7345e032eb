"""``kv_pool_write_ms`` on a small synthetic trace: two recorded rounds of
16 steps, ops under ``kv_write`` and ``layer_carry`` (one of them a
fusion inside the kernel scope), a segment dispatched before the trace
began, and rounds whose ops carry neither scope."""

from benchlib import spans
from benchlib.spans import Op, Span
from metrics import kv_pool_write_ms

SEG = "jit(seg)/serve_segment/decode_phase/while/body/closed_call"
MS = 1e6                                     # ns


def op(scope, start, dur, name="%fusion.1 = s8[16,4] fusion(%p)"):
    return Op(name, f"{SEG}/while/body/closed_call/{scope}/x", start * MS,
              dur * MS)


def round_spans(start, dur):
    return [Span("serve.round", start * MS, dur * MS, {}),
            Span("serve.dispatch", (start + 1) * MS, MS,
                 {"mixed": 0, "steps": 16})]


def test_reads_the_two_scopes_per_step():
    ops = [op("kv_write", 5, 7),                   # before the trace's round
           op("kv_write", 110, 8), op("layer_carry", 130, 4),
           op("attn_kernel", 140, 50),
           op("kv_write/jit(_kv_write)/ita_kv_write", 210, 4,
              "%ita_kv_write.3 = (s8[4,513,32,128,128]) custom-call(%p)"),
           op("mlp", 220, 30)]
    trace = spans.from_events(ops, round_spans(100, 100)
                              + round_spans(205, 100))
    assert kv_pool_write_ms.per_step_ms(trace) == (8 + 4 + 4) / 32


def test_zero_without_the_scopes_and_none_without_rounds():
    quiet = [op("attn_kernel", 110, 50), op("mlp", 170, 20)]
    assert kv_pool_write_ms.per_step_ms(
        spans.from_events(quiet, round_spans(100, 100))) == 0.0
    assert kv_pool_write_ms.per_step_ms(spans.from_events(quiet, [])) is None
    assert kv_pool_write_ms.per_step_ms(None) is None
