"""The process's one profiler session, started and stopped while it runs.

``jax.profiler`` traces the whole process and allows one trace at a time,
so the program owns that one here: the trainer's step window, the
server's ``POST /profile`` and the benchmark's traced phase all call
``start(dir)`` / ``stop()``.  A session

* runs with the Python tracer off — the program's own spans name the host
  side, and the tracer's cost would land inside the traced steps;
* writes one ``TraceAnnotation("obs_clock_sync")`` right after the start
  and keeps the ``perf_counter()`` taken inside it.  That pair joins the
  two clocks: a reader finds the annotation on the trace's ``/host:CPU``
  plane and ``to_trace_ns`` maps any ``perf_counter`` time (a
  ``TraceRecorder`` span, a log event) onto the trace's clock;
* switches on the recorders registered with ``while_profiling`` (the
  train loop's, ``obs.trace.TRAIN_TRACE``, and every serving engine's)
  and restores them at the stop; it keeps them (``Session.recorders``),
  so that a reader of the profile finds the spans, arguments and all,
  that lie on its clock.

A running job is asked for a trace through ``request_steps``: the train
loop polls ``take_step_request`` at the top of every iteration.  Nothing
here imports JAX until a session starts.
"""

from __future__ import annotations

import dataclasses
import threading
import time
import weakref
from typing import List, Optional, Tuple

SYNC_NAME = "obs_clock_sync"

# Every name the program gives its device work — ``jax.named_scope`` at
# the model's block boundaries, the loss and the update, ``name=`` on the
# Pallas kernels — as a profile shows them in an operation's path
# (docs/observability.md).  A reader sorts operations under these;
# tests/obs/test_profile.py holds the tuple to the source.
DEVICE_SCOPES = (
    "embed", "attention", "mlp", "lm_head", "cross_entropy", "grad_accum",
    "optimizer", "kv_cache", "flash_fwd", "flash_bwd_dq", "flash_bwd_dkv",
    "flash_decode", "rmsnorm", "sample",
    # a hybrid stack (models/gated_deltanet.py, models/moe.py): the Gated
    # DeltaNet mixer and its parts, the dropless experts' parts ("mlp"
    # holds them all; the kernel "grouped_experts" runs under
    # "moe_experts")
    "gdn", "gdn_proj", "gdn_conv", "gdn_scan", "gdn_step",
    "moe_router", "moe_dispatch", "moe_experts", "moe_shared",
    "grouped_experts",
    # the Mamba-2 mixer and its parts (models/mamba2.py), and the
    # projections into and out of the experts' latent
    "mamba", "mamba_proj", "mamba_conv", "mamba_scan", "mamba_step",
    "moe_latent",
    # latent attention (models/mla.py), under "attention": its five
    # projections and the absorption, and the absorbed form's kernel (the
    # expanded form runs "flash_fwd")
    "mla_proj", "mla_decode",
    # the kinds of a stack of runs alone and "window"
    # (models/transformer.py:MIXERS): the Mamba-1 mixer and its parts (models/mamba1.py), the window layers
    # ("swa": their flash_fwd under the window, the ring's attention, the
    # kernel "ring_decode", and its row writes), the cross layers
    # ("xattn": their walks of the one cached layer run "flash_decode")
    # and the gated memory units
    "mamba1", "mamba1_proj", "mamba1_conv", "mamba1_scan", "mamba1_step",
    "swa", "ring_decode", "xattn", "gmu")


@dataclasses.dataclass
class Session:
    dir: str
    t_sync: float                    # perf_counter inside the annotation
    t_stop: Optional[float] = None   # perf_counter when recording ended
    recorders: tuple = ()            # the recorders it switched on

    def clock_sync(self) -> dict:
        """What ``TraceRecorder.chrome_trace()`` exports of the session."""
        return {"annotation": SYNC_NAME, "perf_counter": self.t_sync,
                "stop_perf_counter": self.t_stop, "dir": self.dir}


@dataclasses.dataclass
class StepRequest:
    """Trace ``steps`` train iterations into ``dir``, from iteration
    ``first`` (1-based) or, without one, from the next."""
    steps: int
    dir: str
    first: Optional[int] = None


_lock = threading.Lock()
_active: Optional[Session] = None
_last: Optional[Session] = None
# on while a session is active; held weakly: an engine's goes with it
_recorders: "weakref.WeakSet" = weakref.WeakSet()
_restore: List[Tuple[object, bool]] = []
_step_request: Optional[StepRequest] = None


def while_profiling(recorder) -> None:
    """Register a recorder (anything with ``enabled``) that a session
    switches on and its stop puts back as it was."""
    with _lock:
        _recorders.add(recorder)


def active() -> Optional[Session]:
    return _active


def last() -> Optional[Session]:
    """The newest session, running or closed."""
    return _active or _last


def start(dir: str) -> Session:  # noqa: A002 — the profiler's own word
    """Start tracing into ``dir``.  Raises if a session is active."""
    global _active
    import jax

    with _lock:
        if _active is not None:
            raise RuntimeError(
                f"a profile session is already writing to {_active.dir}")
        try:
            options = jax.profiler.ProfileOptions()
            options.python_tracer_level = 0
            jax.profiler.start_trace(str(dir), profiler_options=options)
        except (AttributeError, TypeError):   # a jax without the options
            jax.profiler.start_trace(str(dir))
        with jax.profiler.TraceAnnotation(SYNC_NAME):
            t_sync = time.perf_counter()
        _active = Session(str(dir), t_sync, recorders=tuple(_recorders))
        for rec in _active.recorders:
            _restore.append((rec, rec.enabled))
            rec.enabled = True
        return _active


def stop() -> Session:
    """Stop the active session and write its trace."""
    global _active, _last
    import jax

    with _lock:
        if _active is None:
            raise RuntimeError("no profile session is active")
        session = _active
        # the traced window ends here: collecting and writing the trace
        # takes seconds in which nothing more is recorded
        session.t_stop = time.perf_counter()
        try:
            jax.profiler.stop_trace()
        finally:
            while _restore:
                rec, was = _restore.pop()
                rec.enabled = was
            _active, _last = None, session
        return session


def to_trace_ns(t_perf: float, t_sync: float, sync_start_ns: float) -> float:
    """A ``perf_counter`` time on the clock of the trace whose
    ``obs_clock_sync`` annotation starts at ``sync_start_ns``."""
    return sync_start_ns + (t_perf - t_sync) * 1e9


# --- asking a running train loop for a trace --------------------------------

def request_steps(steps: int, dir: str,  # noqa: A002
                  first: Optional[int] = None) -> None:
    """Ask the train loop to trace ``steps`` iterations into ``dir``.
    From any thread; a newer request replaces one not yet taken."""
    global _step_request
    if steps < 1:
        raise ValueError(f"steps must be at least 1, got {steps}")
    with _lock:
        _step_request = StepRequest(int(steps), str(dir), first)


def cancel_step_request() -> None:
    """Drop a request nobody took (the loop it was meant for has ended)."""
    global _step_request
    with _lock:
        _step_request = None


def take_step_request(next_step: int) -> Optional[StepRequest]:
    """The pending request if it is due at iteration ``next_step`` and no
    session is active, with ``first`` filled in.  A window that lies
    wholly before ``next_step`` (a job resumed past it) is dropped."""
    global _step_request
    if _step_request is None:          # the common case takes no lock
        return None
    with _lock:
        req = _step_request
        if req is None or _active is not None:
            return None
        first = next_step if req.first is None else req.first
        if first > next_step:
            return None
        _step_request = None
        if first + req.steps <= next_step:
            return None
        return StepRequest(first + req.steps - next_step, req.dir, next_step)
