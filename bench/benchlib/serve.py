"""Driving the system under test: one ``serve_continuous`` call per run.

The call serves the warm-up requests, the ramp that fills the slots and
the measured window in one go. A ``WindowClock`` stands in for the
serve loop's drain signal: the loop polls it once per admission round,
which is where the window opens (the first round at or after the mix's
``ramp_steps``) and where it closes (the first round ``seconds`` later).
From then on it asks the loop to drain: no more admissions, in-flight
requests finish, so every request that got a first token in the window
comes back with its stamps.

In a traced run the clock also starts and stops the profiler at round
boundaries, and a ``SegmentRecorder`` keeps what each fused segment
returns about its own steps (tokens granted and emitted per row) plus
each row's position going in, which is what the per-layer readers count
work from.
"""

from __future__ import annotations

import dataclasses
import time

import jax
import jax.numpy as jnp
import numpy as np

COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


def program_config(conf: dict):
    """The program's ``ModelConfig`` holding the configuration file's
    numbers (the registry entry supplies the rest)."""
    from repro.configs.registry import get_config
    pattern = tuple(conf["block_pattern"])
    n_layers = int(conf["num_hidden_layers"])
    if n_layers % len(pattern):
        raise ValueError(f"{n_layers} layers is not a whole number of "
                         f"{pattern} periods")
    serving = conf["serving"]
    return get_config(
        conf["registry"],
        d_model=conf["hidden_size"], n_heads=conf["num_attention_heads"],
        n_kv_heads=conf["num_key_value_heads"], head_dim=conf["head_dim"],
        d_ff=conf["intermediate_size"], vocab_size=conf["vocab_size"],
        layer_groups=((pattern, n_layers // len(pattern)),),
        qkv_bias=bool(conf["attention_bias"]), rope_theta=conf["rope_theta"],
        tie_embeddings=bool(conf["tie_word_embeddings"]),
        attention_impl=serving["attention_impl"], dtype=serving["dtype"])


def param_layout(cfg):
    """Shapes and dtypes of the program's serving weights (nothing is
    computed: ``eval_shape``)."""
    from repro.models import init_serving_params
    return jax.eval_shape(lambda k: init_serving_params(k, cfg),
                          jax.random.PRNGKey(0))


class CompileCounter:
    """Counts XLA backend compiles, and those that fall inside a marked
    interval."""

    def __init__(self):
        self.total = 0
        self.seconds = 0.0
        self.in_window = 0
        self.open = False
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **_):
        if event == COMPILE_EVENT:
            self.total += 1
            self.seconds += duration
            self.in_window += self.open


class WindowClock:
    """The serve loop's drain signal, used as the benchmark's clock."""

    def __init__(self, open_step: int, seconds: float, trace_seconds=None,
                 on_trace_start=None, on_trace_stop=None, compiles=None):
        self.open_step = open_step
        self.seconds = seconds
        self.trace_seconds = trace_seconds
        self._start, self._stop = on_trace_start, on_trace_stop
        self.compiles = compiles
        self.marks: list[tuple[int, float]] = []     # (step, perf_counter)
        self.t_open = self.t_close = None
        self.step_open = self.step_close = None
        self.t_trace = self.t_untrace = None

    def poll(self, step: int) -> bool:
        t = time.perf_counter()
        self.marks.append((step, t))
        if self.t_open is None:
            if step < self.open_step:
                return False
            self.t_open, self.step_open = t, step
            if self.compiles is not None:
                self.compiles.open = True
        if self.t_close is not None:
            return True
        # the trace covers the window's last ``trace_seconds``, so that
        # writing it out falls after the window has closed
        if self._start is not None and self.t_trace is None and \
                t - self.t_open >= self.seconds - self.trace_seconds:
            self._start()
            self.t_trace = time.perf_counter()
        if t - self.t_open < self.seconds:
            return False
        self.t_close, self.step_close = t, step
        if self.compiles is not None:
            self.compiles.open = False
        if self.t_trace is not None:
            self.t_untrace = t
            self._stop()
        return True


@dataclasses.dataclass
class SegmentCall:
    mixed_steps: int          # leading chunk-wide steps of the segment
    pos_in: object            # (B,) positions going in (device copy)
    grants: object            # (B, segment) tokens processed per row, step
    emits: object             # (B, segment) tokens emitted per row, step


class SegmentRecorder:
    """Wraps the serve loop's segment builder so each dispatched segment
    leaves a ``SegmentCall`` while ``active``. Recording only keeps
    references to what the segment already returns, plus one copy of the
    slot positions; any mismatch with the expected interface switches it
    off (``broken``) and never touches the serve."""

    def __init__(self):
        self.calls: list[SegmentCall] = []
        self.active = False
        self.broken = None

    def install(self, slots: int):
        import repro.runtime.generate as gen
        orig = getattr(gen, "_serve_segment_fn", None)
        if orig is None:
            self.broken = "runtime.generate has no _serve_segment_fn"
            return lambda: None
        rec = self

        def builder(cfg, segment, sample, eos_id, pad_id, chunk=None,
                    budget=None, mixed_steps=None):
            fn = orig(cfg, segment, sample, eos_id, pad_id, chunk, budget,
                      mixed_steps)
            k = 0 if chunk is None else min(mixed_steps or segment, segment)

            def call(params, state, caches, temperature, *rest):
                pos = None
                if rec.active and rec.broken is None:
                    try:
                        pos = jnp.copy(state.pos)
                    except AttributeError as e:
                        rec.broken = f"segment state: {e}"
                out = fn(params, state, caches, temperature, *rest)
                if pos is not None:
                    try:
                        rec.calls.append(SegmentCall(k, pos, out[2], out[1]))
                    except (IndexError, TypeError) as e:
                        rec.broken = f"segment outputs: {e}"
                return out
            return call

        jnp.copy(jnp.zeros((slots,), jnp.int32))  # build the copy now
        gen._serve_segment_fn = builder
        return lambda: setattr(gen, "_serve_segment_fn", orig)

    def host_calls(self):
        """The recorded calls with their arrays read back."""
        return [SegmentCall(c.mixed_steps, np.asarray(c.pos_in),
                            np.asarray(c.grants), np.asarray(c.emits))
                for c in self.calls]


def serve(params, cfg, requests, mix, pool_pages, clock):
    """One ``serve_continuous`` call over ``requests`` under the mix's
    serving geometry, with ``clock`` as its drain signal."""
    from repro.runtime.generate import ServeRequest, serve_continuous
    geo = mix["geometry"]
    reqs = [ServeRequest(prompt=r.prompt, gen=r.gen, arrival=r.arrival,
                         request_id=r.rid) for r in requests]
    return serve_continuous(
        params, cfg, reqs, slots=geo["slots"], segment=geo["segment"],
        max_len=geo["max_len"], page_size=geo["page_size"],
        num_pages=pool_pages, admission="chunked",
        chunk_size=geo["chunk_size"], token_budget=geo["token_budget"],
        drain=clock)
