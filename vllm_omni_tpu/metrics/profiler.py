"""Profiling: jax.profiler traces fanned out across pipeline stages.

The TPU counterpart of the reference's torch-profiler RPC chain
(reference: Omni.start_profile/stop_profile entrypoints/omni.py:398-497 →
stage PROFILER_START/STOP tasks omni_stage.py:740-777 →
DiffusionEngine.start_profile collective_rpc diffusion_engine.py:197-313 →
per-rank TorchProfiler, diffusion/profiler/torch_profiler.py:17).

Here each stage owns one ``StageProfiler`` that wraps
``jax.profiler.start_trace/stop_trace``: traces land under
``{trace_dir}/stage_{id}`` in XPlane format (TensorBoard / xprof
readable).  Cross-process stages receive the same start/stop over their
command socket (entrypoints/stage_proc.py).
"""

from __future__ import annotations

import os
from typing import Optional

from vllm_omni_tpu.logger import init_logger

logger = init_logger(__name__)


# jax.profiler admits ONE active trace per process; in-proc pipelines run
# every stage in the same process, so the first stage's trace covers them
# all and later starts are no-ops (process-disaggregated stages each own
# a process and trace independently)
_process_owner: Optional[int] = None


class StageProfiler:
    """Per-stage jax.profiler session (one active trace at a time)."""

    def __init__(self, stage_id: int):
        self.stage_id = stage_id
        self._active_dir: Optional[str] = None

    @property
    def active(self) -> bool:
        return self._active_dir is not None

    def start(self, trace_dir: str) -> Optional[str]:
        """Begin an XPlane trace under ``trace_dir/stage_{id}``; returns
        the stage's trace directory.  Idempotent while active; a no-op
        when another in-process stage already owns the process trace."""
        global _process_owner
        if self._active_dir is not None:
            logger.warning(
                "stage %d: profiler already tracing to %s",
                self.stage_id, self._active_dir,
            )
            return self._active_dir
        if _process_owner is not None:
            logger.info(
                "stage %d: stage %d's trace already covers this process",
                self.stage_id, _process_owner,
            )
            return None
        import jax

        path = os.path.join(trace_dir, f"stage_{self.stage_id}")
        os.makedirs(path, exist_ok=True)
        # the Python tracer stays off: recording every call of every
        # thread slowed the traced steps of a serving engine by a quarter
        # and tripled the device's idle share (PERF.md, PR 25), so a
        # trace taken with it says little of the untraced program.  The
        # host's phases are in the trace by name instead (``omni.*``
        # annotations, docs/observability.md)
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        jax.profiler.start_trace(path, profiler_options=options)
        _process_owner = self.stage_id
        self._active_dir = path
        logger.info("stage %d: profiling -> %s", self.stage_id, path)
        return path

    def stop(self) -> Optional[str]:
        """End the trace; returns the directory the trace landed in (None
        if this stage owned no trace)."""
        global _process_owner
        if self._active_dir is None:
            return None
        import jax

        jax.profiler.stop_trace()
        _process_owner = None
        path, self._active_dir = self._active_dir, None
        logger.info("stage %d: profile written to %s", self.stage_id, path)
        return path
