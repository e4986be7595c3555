"""TPU platform (role of reference's platforms/cuda/platform.py:15 — picks
worker classes and attention backends per device capability)."""

from __future__ import annotations

from vllm_omni_tpu import envs
from vllm_omni_tpu.platforms.interface import OmniPlatform


class TpuPlatform(OmniPlatform):
    name = "tpu"
    supports_pallas = True

    def ar_attention_backend(self) -> str:
        override = envs.OMNI_TPU_AR_ATTENTION_BACKEND
        if override != "auto":
            return override
        return "pallas_paged"

    def diffusion_attention_backend(self) -> str:
        override = envs.OMNI_TPU_DIFFUSION_ATTENTION_BACKEND
        if override != "auto":
            return override
        return "pallas_flash"

    # (peak dense bf16 TFLOP/s, peak HBM GB/s) per chip generation, from
    # Google Cloud's published TPU specifications; MFU / MBU
    # denominators.  One table so a new generation cannot land in one
    # metric and not the other.
    _PEAK_TABLE = {
        "v4": (275.0, 1228.0),
        "v5 lite": (197.0, 819.0), "v5e": (197.0, 819.0),
        "v5litepod": (197.0, 819.0), "v5p": (459.0, 2765.0),
        "v6 lite": (918.0, 1640.0), "v6e": (918.0, 1640.0),
    }

    def _peaks(self) -> tuple:
        kind = self.device_kind().lower()
        for k, v in self._PEAK_TABLE.items():
            if k in kind:
                return v
        raise ValueError(
            f"device_kind {self.device_kind()!r} is not in TpuPlatform."
            "_PEAK_TABLE — add its published peaks; none is assumed")

    def peak_tflops_bf16(self) -> float:
        return self._peaks()[0]

    def peak_hbm_gbps(self) -> float:
        return self._peaks()[1]

    def stage_device_env(self, devices: str = "all") -> dict:
        """libtpu chip scoping for a spawned stage worker.  "all": the
        worker takes every chip — no scoping; its parent must never
        touch JAX (one process per chip).  One chip id: that chip as a
        one-process slice of its own, on a port of its own, with
        libtpu's one-load-per-host guard lifted so sibling workers can
        each hold their chip (shown to work on a four-chip v5e host with
        libtpu 0.0.34, CHANGES.md PR 21).  A larger subset needs
        per-topology bounds nobody has run: refused, not guessed — give
        such a stage every chip and ``tensor_parallel_size`` instead."""
        if devices in ("", "all"):
            return {}
        chips = [d for d in devices.split(",") if d]
        if len(chips) != 1:
            raise ValueError(
                f"stage devices {devices!r}: scoping a process to a "
                "multi-chip subset is not supported on TPU (only \"all\" "
                "or one chip id)")
        return {"TPU_VISIBLE_CHIPS": chips[0],
                "TPU_PROCESS_BOUNDS": "1,1,1",
                "TPU_CHIPS_PER_PROCESS_BOUNDS": "1,1,1",
                "TPU_PROCESS_PORT": str(8476 + int(chips[0])),
                "ALLOW_MULTIPLE_LIBTPU_LOAD": "1"}
