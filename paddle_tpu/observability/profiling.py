"""On-demand device profiling: windowed ``jax.profiler`` captures.

The always-on layer (metrics + spans) tells you a step is slow; the
question that follows — "what did the DEVICE actually execute" — needs
a real profiler trace, which is far too heavy to leave running. This
module is the control plane for capturing one on demand, windowed to a
step count, from a live job:

- ``GET /control/profile?steps=N`` on the exposition server (or
  :func:`request_capture` in-process, or ``SIGUSR2`` after
  :func:`install_sigusr2`) ARMS a capture;
- the capture starts at the next step boundary (``step_tick`` is wired
  into ``LLMEngine.step`` and ``ResilientTrainLoop``) and stops after
  ``N`` steps, so the trace covers whole steps, never a torn window;
- while a capture is live, ``trace_span`` additionally emits
  ``jax.profiler.TraceAnnotation`` so the host-side spans land INSIDE
  the device trace — Perfetto shows which device ops ran under which
  engine phase;
- a capture costs the run nothing it need not: it is taken WITHOUT the
  profiler's Python tracer (the host tracer that ``TraceAnnotation``
  needs stays on), and the step that counts the window down hands
  ``stop_trace()`` to a helper thread and returns at once — collecting
  and writing the trace no longer stalls the step thread;
- right after the start one ``serving.clock_anchor`` annotation carries
  the ``perf_counter`` reading as its argument, and at the stop one
  ``serving.profile_capture`` span lands in the ring (only there): any
  ring span can be put on the trace's timeline, and a reader knows the
  traced stretch exactly;
- each completed capture lands in the flight recorder
  (``profile_capture`` event) and bumps ``obs_profile_captures_total``.

``step_tick`` costs one attribute read when idle — the hot loops call
it unconditionally. jax is imported only when a capture actually
starts (the package's no-heavy-deps contract holds until then).

The control plane itself is deliberately OUTSIDE the
``FLAGS_obs_enabled`` gate: a capture is an explicit operator action
and works on a job running with observability off. What needs the flag
ON is the telemetry AROUND the capture — the
``obs_profile_captures_total`` bump, the ``profile_capture`` flight
event, and the host-span → ``TraceAnnotation`` correlation (a disabled
``trace_span`` never runs its body, so the device trace shows raw ops
with no host phases). For correlated traces, enable observability
before arming.
"""
from __future__ import annotations

import os
import signal
import tempfile
import threading
import time
from typing import Dict, Optional

from ..framework.flags import get_flag
from . import state, tracing
from .catalog import instrument as _instrument

__all__ = ["ProfileController", "get_controller",
           "get_profile_controller", "request_capture", "step_tick",
           "install_sigusr2", "uninstall_sigusr2"]

# FLAGS_obs_profile_dir / obs_profile_default_steps are defined in the
# package __init__ (this module is lazily loaded; the flags must
# register up front so set_flags sees them).

# A capture this old ends at the next step boundary unless its request
# names another bound. The serving captures the benchmark reads end in
# under 5.1 s (240 engine steps; PERF.md); stopping a capture takes about
# as long as it ran.
MAX_SECONDS = 6.0

_M_CAPTURES = _instrument("obs_profile_captures_total")

class ProfileController:
    """Arm/step/stop state machine for windowed device captures.

    ``_pending`` is the instance's idle fast path: hot loops read it
    (one attribute load) before touching the lock. ``_sig_armed`` is
    the SIGUSR2 deferral flag — the signal handler must not take the
    non-reentrant lock (the main thread may already hold it inside
    step_tick), so it only sets flags and the next step boundary arms
    the capture on the handler's behalf.

    The stop runs on a helper thread that is handed the lock and holds
    it until the file is written. ``_stopping`` is read without the lock
    so that a second ``request`` is refused at once; ``status()`` takes
    the lock, so it waits while the profiler starts or stops and never
    answers ``active: False`` before the capture is on disk (a poller's
    clock reading taken BEFORE the call is when the stop was asked
    for)."""

    def __init__(self):
        self._lock = threading.Lock()
        self._pending = False
        self._stopping = False
        self._t_started = 0.0       # perf_counter when the trace began
        self._sig_armed = False
        self._steps_left = 0
        self._armed_n = 0
        self._max_s = 0.0           # the capture's bound in seconds
        self._active = False
        self._dir: Optional[str] = None
        self._seq = 0
        self._last: Optional[Dict] = None

    # -- control ----------------------------------------------------------
    def request(self, steps: Optional[int] = None,
                out_dir: Optional[str] = None,
                seconds: Optional[float] = None) -> Dict:
        """Arm a capture spanning ``steps`` step boundaries, or as many
        whole steps as end within ``seconds``. Returns a status dict
        (also the ``/control/profile`` response body). A second request
        while one is armed/active is rejected — two overlapping jax
        traces would abort the first."""
        n = int(steps) if steps is not None else int(
            get_flag("obs_profile_default_steps"))
        if n <= 0:
            return {"ok": False, "bad_request": True,
                    "error": f"steps must be > 0, got {n}"}
        max_s = float(seconds) if seconds is not None else MAX_SECONDS
        if not max_s > 0:
            return {"ok": False, "bad_request": True,
                    "error": f"seconds must be > 0, got {max_s}"}
        if self._stopping:
            # the helper thread holds the lock while it writes the file
            return {"ok": False, "error": "capture already in flight",
                    "status": self._status_locked()}
        with self._lock:
            if self._active or self._steps_left > 0:
                return {"ok": False, "error": "capture already in flight",
                        "status": self._status_locked()}
            self._steps_left = n
            self._armed_n = n
            self._max_s = max_s
            self._seq += 1
            self._dir = self._derive_dir(out_dir)
            self._pending = True
            return {"ok": True, "armed_steps": n, "max_seconds": max_s,
                    "dir": self._dir,
                    "status": self._status_locked()}

    def _derive_dir(self, out_dir: Optional[str]) -> str:
        if out_dir:
            return out_dir
        flag = str(get_flag("obs_profile_dir"))
        if flag:
            return os.path.join(flag, f"capture-{self._seq}")
        return os.path.join(
            tempfile.gettempdir(),
            f"paddle_tpu_profile-{os.getpid()}-{self._seq}")

    def step_tick(self) -> None:
        """One engine/train step boundary. Starts the armed capture,
        counts down, stops at zero or where the capture is overdue.
        Called with ``_pending`` true only."""
        if self._stopping:
            return      # a SIGUSR2 during a stop waits for the next step
        if self._sig_armed:
            # a SIGUSR2 landed since the last boundary: arm the default
            # window HERE, outside signal context (see __init__ docstring)
            self._sig_armed = False
            self.request()
        # the lock is taken by hand: the step that ends the window does
        # not give it back but hands it to the helper that writes the
        # trace (a threading.Lock may be released by another thread), so
        # no caller can slip in between the stop asked for and the file
        self._lock.acquire()
        handed = False
        try:
            if not self._active:
                if self._steps_left <= 0:
                    self._pending = False
                    return
                self._start_locked()
                return
            self._steps_left -= 1
            if self._steps_left <= 0 or \
                    time.perf_counter() - self._t_started >= self._max_s:
                self._pending = False
                self._stop_locked(wait=False)
                handed = True
        finally:
            if not handed:
                self._lock.release()

    def stop(self) -> Dict:
        """Force-stop (an idle job whose armed capture never saw a
        step, or an operator cutting a window short). Waits for a stop
        already under way: on return the capture is on disk."""
        with self._lock:
            if self._active:
                self._stop_locked(wait=True)
            self._steps_left = 0
            self._sig_armed = False
            self._pending = False
            return self._status_locked()

    def status(self) -> Dict:
        with self._lock:
            return self._status_locked()

    def _status_locked(self) -> Dict:
        out = {"active": self._active, "steps_left": self._steps_left,
               "dir": self._dir, "last_capture": self._last}
        if self._sig_armed:
            out["sig_armed"] = True
        return out

    # -- capture plumbing (lock held) -------------------------------------
    def _start_locked(self) -> None:
        try:
            import jax

            os.makedirs(self._dir, exist_ok=True)
            # no Python tracer: it slows the host enough to double the
            # traced stretch's idle share (PERF.md, PR 25); the host
            # tracer (TraceAnnotation) keeps its default
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            jax.profiler.start_trace(self._dir, profiler_options=opts)
        except Exception as e:            # no backend / second profiler
            self._steps_left = 0
            self._last = {"ok": False, "error": repr(e), "dir": self._dir}
            from . import flight_recorder

            flight_recorder.record("profile_capture_failed",
                                   dir=self._dir, error=repr(e))
            return
        self._active = True
        # one event that is on both clocks: its start on the trace's,
        # perf_counter (the span ring's) as its argument
        self._t_started = time.perf_counter()
        with jax.profiler.TraceAnnotation(
                "serving.clock_anchor", perf_counter=self._t_started):
            pass
        # host spans correlate with device ops only while capturing:
        # trace_span wraps its body in a TraceAnnotation via this hook
        tracing._set_annotation_factory(_annotation)

    def _stop_locked(self, wait: bool) -> None:
        """End the traced stretch now; collect and write the trace on
        this thread (``wait``) or on a helper thread, which then gives
        back the lock the caller holds."""
        tracing._set_annotation_factory(None)
        if state.enabled():
            # ring only, on the perf_counter clock: the traced stretch
            tracing.get_tracer().record(
                "serving.profile_capture", self._t_started,
                time.perf_counter(),
                {"dir": self._dir, "steps": self._traced_steps()},
                depth=0)
        if wait:
            self._write_trace()
            return
        self._stopping = True
        threading.Thread(target=self._write_trace_and_release,
                         name="obs-profile-stop", daemon=True).start()

    def _write_trace_and_release(self) -> None:
        try:
            self._write_trace()
        finally:
            self._lock.release()

    def _traced_steps(self) -> int:
        """Whole steps of the capture so far: the armed count unless it
        was cut short (overdue, or a forced stop)."""
        return self._armed_n - max(0, self._steps_left)

    def _write_trace(self) -> None:
        steps = self._traced_steps()
        self._steps_left = 0
        try:
            import jax

            jax.profiler.stop_trace()
        except Exception as e:
            self._last = {"ok": False, "error": repr(e), "dir": self._dir}
            return
        finally:
            self._active = False
            self._stopping = False
        dur = time.perf_counter() - self._t_started
        self._last = {"ok": True, "dir": self._dir,
                      "seconds": dur, "unix_time": time.time()}
        _M_CAPTURES.inc()
        from . import flight_recorder

        flight_recorder.record("profile_capture", dir=self._dir,
                               seconds=round(dur, 6), steps=steps)


def _annotation(name: str):
    import jax

    return jax.profiler.TraceAnnotation(name)


_default_controller = ProfileController()


def get_controller() -> ProfileController:
    return _default_controller


# the name the package re-exports (observability.get_profile_controller;
# `get_controller` alone would shadow poorly next to tracing.get_tracer)
get_profile_controller = get_controller


def request_capture(steps: Optional[int] = None,
                    out_dir: Optional[str] = None,
                    seconds: Optional[float] = None) -> Dict:
    """Arm a windowed device capture on the default controller."""
    return _default_controller.request(steps=steps, out_dir=out_dir,
                                       seconds=seconds)


def step_tick() -> None:
    """The per-step hook: near-zero while nothing is armed (one
    attribute read on the default controller), drives the capture
    window when something is."""
    if not _default_controller._pending:
        return
    _default_controller.step_tick()


_prev_sigusr2 = None


def install_sigusr2() -> bool:
    """``kill -USR2 <pid>`` arms a default-window capture — the
    no-HTTP-access escape hatch. Main-thread only (signal module
    contract); returns False where that fails."""
    global _prev_sigusr2
    if _prev_sigusr2 is not None:
        return True

    def handler(_signum, _frame):
        # flags only: the handler runs between bytecodes on the main
        # thread, which may hold the controller lock (step_tick holds
        # it across start/stop_trace) — request() here would deadlock.
        # The next step boundary arms the window instead.
        _default_controller._sig_armed = True
        _default_controller._pending = True

    try:
        _prev_sigusr2 = signal.signal(signal.SIGUSR2, handler)
        return True
    except (ValueError, OSError, AttributeError):
        return False


def uninstall_sigusr2() -> None:
    global _prev_sigusr2
    if _prev_sigusr2 is not None:
        signal.signal(signal.SIGUSR2, _prev_sigusr2)
        _prev_sigusr2 = None
