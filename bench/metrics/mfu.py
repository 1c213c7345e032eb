"""Model step: the traced window's useful work as a share of the chip's
peak, in percent. Useful tokens are those the steps processed for live
rows (prompt tokens prefilled and decode tokens; no padding rows). Their
dense matmuls count at the bf16 peak, their attention at the int8 peak,
as time at peak, over the traced window."""


def read(run):
    if not run.steps or not run.trace_window_s:
        return None
    m, p = run.model, run.peaks
    t = 0.0
    for s in run.steps:
        t += m.dense_flops(int(s.q.sum()), s.emitted) / p["bf16_flops"]
        t += m.attn_ops(s.q, s.kv) / p["int8_ops"]
    return 100.0 * t / run.trace_window_s
