"""Model step: bytes the traced window's steps must read, over the
window times the chip's HBM bandwidth, in percent. A step must read
every weight once and the int8 K/V of each live row's context."""


def read(run):
    if not run.steps or not run.trace_window_s:
        return None
    m = run.model
    total = sum(m.step_weight_bytes + float(s.kv.sum()) * m.kv_bytes_per_token
                for s in run.steps)
    return 100.0 * total / (run.trace_window_s * run.peaks["hbm_bytes_per_s"])
