"""Opt-in step-bounded ``jax.profiler`` trace capture.

``--profile DIR`` (training/__init__.py) already traces the WHOLE run;
that is the wrong tool past the first epochs - a 20-epoch run's xplane
dir is dominated by compile + warm-up and dwarfs the steady-state steps
the user wants to look at.  ``--profile-steps A:B`` bounds the capture
to optimizer steps ``[A, B)``: the trace starts right before step A's
dispatch and stops after step B-1's program completes (the trainer
fences on the step's outputs before stopping, so the device work is in
the trace).  The capture does not change the program: where an epoch
runs as one scanned program, the trace starts before the first epoch
that holds a step of the range and stops after the fetches of the last
one (``Trainer._train_epoch``).

On the CPU (the test platform, where a profiler may be absent) a
capture that cannot start logs one warning and is skipped for the rest
of the run.  On an accelerator it raises: the trace is what the user
asked the run for, and a run that silently has none wasted the chip.
"""

from __future__ import annotations

import logging
import os
from pathlib import Path

log = logging.getLogger(__name__)


class StepTraceCapture:
    """Start/stop ``jax.profiler`` around a step range ``[start, stop)``."""

    def __init__(self, trace_dir, start: int, stop: int):
        if start < 0 or stop <= start:
            raise ValueError(
                f"profile step range must satisfy 0 <= A < B, got "
                f"{start}:{stop}"
            )
        self.trace_dir = Path(trace_dir)
        self.start = int(start)
        self.stop = int(stop)
        self._active = False
        self._captured = False
        self._disabled = False

    # -- construction --------------------------------------------------------

    @classmethod
    def parse_range(cls, spec: str) -> tuple[int, int]:
        """``"A:B"`` -> ``(A, B)`` with loud failure on malformed specs."""
        head, sep, tail = str(spec).partition(":")
        if not sep:
            raise ValueError(
                f"--profile-steps wants A:B (half-open step range), got "
                f"{spec!r}"
            )
        try:
            start, stop = int(head), int(tail)
        except ValueError as exc:
            raise ValueError(
                f"--profile-steps wants integer steps A:B, got {spec!r}"
            ) from exc
        if start < 0 or stop <= start:
            raise ValueError(
                f"--profile-steps needs 0 <= A < B, got {spec!r}"
            )
        return start, stop

    @classmethod
    def resolve(cls, args) -> "StepTraceCapture | None":
        """From the CLI surface: ``--profile-steps A:B`` bounds a capture
        into the ``--profile DIR`` trace directory; returns ``None`` when
        the flag is absent."""
        spec = getattr(args, "profile_steps", None)
        if not spec:
            return None
        trace_dir = getattr(args, "profile", None)
        if not trace_dir:
            raise SystemExit(
                "--profile-steps bounds a capture and needs --profile DIR "
                "for the trace directory"
            )
        start, stop = cls.parse_range(spec)
        return cls(trace_dir, start, stop)

    # -- step hooks ----------------------------------------------------------

    def on_step_start(self, step: int, count: int = 1) -> None:
        """Before steps ``[step, step + count)`` are dispatched (the
        scanned epoch dispatches its steps as one program): start the
        capture if any of them lies in the range."""
        if self._disabled or self._active or self._captured:
            return
        if step + count <= self.start or step >= self.stop:
            return
        import jax

        try:
            os.makedirs(self.trace_dir, exist_ok=True)
            jax.profiler.start_trace(str(self.trace_dir))
        except Exception as exc:
            if jax.default_backend() != "cpu":
                raise
            # no profiler on the CPU test platform: skip, loudly
            self._disabled = True
            log.warning(
                f"profiler trace capture unavailable on this backend "
                f"({type(exc).__name__}: {exc}); skipping --profile-steps"
            )
            return
        self._active = True

    def on_step_end(self, step: int, fence_value=None) -> None:
        if not self._active or step < self.stop - 1:
            return
        self._stop_trace(fence_value)

    def _stop_trace(self, fence_value=None) -> None:
        try:
            import jax

            if fence_value is not None:
                # the step's device work must have landed before the
                # trace closes, or the capture ends mid-program
                jax.block_until_ready(fence_value)
            jax.profiler.stop_trace()
            self._captured = True
        except Exception as exc:  # pragma: no cover - backend-specific
            self._disabled = True
            log.warning(f"profiler stop_trace failed: {exc}")
        self._active = False

    def close(self) -> dict:
        """Stop any in-flight capture (run ended inside the range);
        returns the ``profile`` telemetry event payload."""
        if self._active:
            self._stop_trace()
        return {
            "dir": str(self.trace_dir),
            "start": self.start,
            "stop": self.stop,
            "captured": self._captured,
        }
