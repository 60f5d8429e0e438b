"""What the program names in a profile: one `jax.named_scope` per layer
of the train step on the device, and the launcher loop's host spans and
compile counts.

Device side.  Every layer of the step runs under one of `SCOPES`, so
each instruction of the compiled step carries the layer in its
``metadata={op_name=...}`` path (forward ``jit(train_step)/.../attn/...``,
backward ``transpose(jvp(attn))``, remat's recompute under
``rematted_computation``), and a profile's device ops can be put down
to a layer by instruction name.  A scope changes only metadata: the
compiled program is the same.

Host side.  `span` is a `jax.profiler.TraceAnnotation` named
``repro.<name>``: it costs nothing unless a profile is being taken,
and then lies on the same clock as the device trace.  `compile_counts`
counts the process's backend compiles and persistent-cache loads
(`jax.monitoring` events), so a step span can say whether that step
compiled.  JAX reports a compile event around every executable it
obtains, loaded from the cache or not, and a cache event inside it for
each load; a compile is the difference.
"""
from __future__ import annotations

import contextlib
import threading

import jax

ATTN = "attn"            # QKV/O projections, RoPE, blockwise attention
FFN = "ffn"              # MLP or MoE up/down projections, activation
EMBED = "embed"          # token gather (and its scatter-add gradient)
LM_HEAD = "lm_head"      # final norm, head matmul, log-sum-exp loss
BOUNDARY = "boundary"    # AQ-SGD / DirectQ codec at a stage boundary
STORE = "store"          # per-sample gather/scatter of the message store
ADAMW = "adamw"          # the optimizer update
SCOPES = (ATTN, FFN, EMBED, LM_HEAD, BOUNDARY, STORE, ADAMW)

SPAN_PREFIX = "repro."
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
CACHE_EVENT = "/jax/compilation_cache/cache_retrieval_time_sec"


def span(name: str, **args) -> jax.profiler.TraceAnnotation:
    """A host span ``repro.<name>`` with ``args`` as its arguments."""
    return jax.profiler.TraceAnnotation(SPAN_PREFIX + name, **args)


@contextlib.contextmanager
def step_span(step: int):
    """The span ``repro.step`` around one step of a launcher loop.  It
    carries ``step_num`` and the compile counts as they stand when the
    step ends, so a step that compiled reads one more than the step
    before it."""
    _listen()                        # so the first step's compile counts
    with jax.profiler.StepTraceAnnotation(SPAN_PREFIX + "step",
                                          step_num=step) as ann:
        try:
            yield
        finally:
            ann.set_metadata(**compile_counts())


class StepProfile:
    """A `jax.profiler` trace of steps ``first`` to ``stop - 1`` of a
    launcher loop, written under ``log_dir``: started when step
    ``first`` begins, stopped when step ``stop`` would begin or the
    loop ends (`close`).  One trace per run."""

    def __init__(self, log_dir: str, first: int, stop: int):
        self.log_dir, self.first, self.stop = log_dir, first, stop
        self.active = self.taken = False

    def at(self, step: int) -> None:
        """Call as step ``step`` begins."""
        if self.active and step >= self.stop:
            self.close()
        elif not self.taken and self.first <= step < self.stop:
            jax.profiler.start_trace(self.log_dir)
            self.active = self.taken = True

    def close(self) -> None:
        if self.active:
            jax.profiler.stop_trace()
            self.active = False


_EVENTS = {COMPILE_EVENT: "obtained", CACHE_EVENT: "cache_hits"}
_COUNTS = dict.fromkeys(_EVENTS.values(), 0)
_LOCK = threading.Lock()
_listening = False


def _on_duration(event: str, duration: float, **kw) -> None:
    if event in _EVENTS:
        with _LOCK:
            _COUNTS[_EVENTS[event]] += 1


def _listen() -> None:
    """Register the `jax.monitoring` listener, once per process
    (listeners cannot be removed)."""
    global _listening
    if _listening:
        return
    with _LOCK:
        if not _listening:
            jax.monitoring.register_event_duration_secs_listener(
                _on_duration)
            _listening = True


def compile_counts() -> dict:
    """{"compiles": n, "cache_hits": n}: the executables the process
    compiled and those it loaded from the persistent compilation cache,
    since the first call of this or of `step_span`."""
    _listen()
    with _LOCK:
        hits = _COUNTS["cache_hits"]
        return {"compiles": _COUNTS["obtained"] - hits, "cache_hits": hits}
