"""Device profiles: the paper's heterogeneous GPU fleet (Table 1) plus the
TPU generations this framework targets.

The inference/startup cost models are deliberately simple and *calibrated*
against the paper's measured quantities (its RQ1 and RQ2 figures):
a profile gives peak compute, HBM bandwidth, host-link bandwidth and disk
read bandwidth; task times are derived, then two global calibration knobs
(framework warm-up seconds, per-inference overhead) are fit so the RQ1
static-resource run lands on the paper's 10.4k/5.3k/2.9k seconds.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Dict, List

from repro.core.context import GB, ContextRecipe


@dataclass(frozen=True)
class DeviceProfile:
    name: str
    year: int
    fp16_tflops: float          # peak half-precision TFLOP/s
    hbm_gb: float
    hbm_gbps: float             # GB/s
    pcie_gbps: float            # host -> device GB/s
    disk_gbps: float            # local disk read GB/s
    cluster_count: int = 0      # paper Table 1 census

    mfu: float = 0.25           # achieved fraction of peak (small-batch)
    bw_eff: float = 0.6         # achieved fraction of HBM bw (decode)


# ---- paper Table 1 (567 GPUs total; 8 major models = 75%) -----------------
PAPER_TABLE_1: Dict[str, DeviceProfile] = {
    "quadro-rtx-6000": DeviceProfile("quadro-rtx-6000", 2018, 32.6, 24, 672,
                                     12, 1.5, cluster_count=106),
    "a10": DeviceProfile("a10", 2021, 125.0, 24, 600, 16, 2.0,
                         cluster_count=78),
    "titan-x-pascal": DeviceProfile("titan-x-pascal", 2016, 11.0, 12, 480,
                                    8, 0.8, cluster_count=69),
    "gtx-1080-ti": DeviceProfile("gtx-1080-ti", 2017, 11.3, 11, 484, 8, 0.8,
                                 cluster_count=63),
    "rtx-6000-ada": DeviceProfile("rtx-6000-ada", 2022, 91.1, 48, 960, 16,
                                  3.0, cluster_count=36),
    "gtx-titan-x": DeviceProfile("gtx-titan-x", 2015, 6.7, 12, 336, 8, 0.6,
                                 cluster_count=34),
    "a40": DeviceProfile("a40", 2020, 149.7, 48, 696, 16, 2.0,
                         cluster_count=26),
    "h100": DeviceProfile("h100", 2023, 989.0, 80, 3350, 55, 6.0,
                          cluster_count=15),
}

# ---- TPU targets -----------------------------------------------------------
TPU_PROFILES: Dict[str, DeviceProfile] = {
    "tpu-v4": DeviceProfile("tpu-v4", 2021, 275.0, 32, 1200, 32, 3.0),
    "tpu-v5e": DeviceProfile("tpu-v5e", 2023, 197.0, 16, 819, 32, 3.0),
    "tpu-v5p": DeviceProfile("tpu-v5p", 2023, 459.0, 95, 2765, 32, 3.0),
    "tpu-v6e": DeviceProfile("tpu-v6e", 2024, 918.0, 32, 1640, 32, 3.0),
}

PROFILES: Dict[str, DeviceProfile] = {**PAPER_TABLE_1, **TPU_PROFILES}

CLUSTER_TOTAL_GPUS = 567


def cluster_census() -> List[str]:
    """One entry per GPU of the 8 major models (the 75% slice of 567)."""
    out: List[str] = []
    for name, p in PAPER_TABLE_1.items():
        out.extend([name] * p.cluster_count)
    return out


# ---- cost models ------------------------------------------------------------
@dataclass(frozen=True)
class CostModel:
    """Calibration knobs shared across profiles.

    Fit against the paper's RQ1/RQ2 figures (tests/test_cluster_sim.py
    pins the shapes they give): full-context 2.9 ks @ bs=100 pins
    (inference_overhead, task_overhead); partial-vs-full pins the disk->GPU
    load; agnostic-vs-partial pins the shared-FS fetch, whose conda-env
    portion pays a small-file metadata penalty (the paper cites metaFS
    storms) that P2P transfers avoid by shipping the packed template.
    """

    framework_warmup_s: float = 16.0     # CUDA/XLA init, imports
    inference_overhead_s: float = 0.30   # python/task-layer per inference
    task_overhead_s: float = 0.05        # dispatch + result upload per task
    prompt_tokens: int = 48
    gen_tokens: int = 4
    param_bytes_per_weight: int = 2
    env_smallfile_factor: float = 7.0    # FS fetch penalty on the env payload
    page_cache_factor: float = 0.15      # repeat disk reads hit the OS cache
    page_cache_evict_s: float = 15.0     # long tasks evict the cached bytes


def fs_fetch_bytes(recipe: ContextRecipe, cost: CostModel) -> int:
    """Effective bytes of a shared-FS cold fetch (env small-file penalty)."""
    return int(recipe.artifact_bytes +
               recipe.env_bytes * cost.env_smallfile_factor)


def load_seconds(profile: DeviceProfile, recipe: ContextRecipe,
                 cost: CostModel, from_disk: bool,
                 page_cached: bool = False,
                 include_warmup: bool = True) -> float:
    """disk -> host RAM -> HBM (+ framework warm-up). The paper's
    'minutes-long' startup, minus the network fetch handled separately.
    ``include_warmup=False`` for the 2nd..Nth context of a multi-context
    start: the CUDA/XLA init is paid once per process, not per context."""
    t = cost.framework_warmup_s if include_warmup else 0.0
    if from_disk:
        factor = cost.page_cache_factor if page_cached else 1.0
        t += factor * recipe.transfer_bytes / (profile.disk_gbps * GB)
    t += recipe.host_bytes / (profile.pcie_gbps * GB)
    return t


def inference_seconds(profile: DeviceProfile, recipe: ContextRecipe,
                      cost: CostModel) -> float:
    """One claim verification: short prefill + few decode tokens, batch 1."""
    n_params = recipe.device_bytes / cost.param_bytes_per_weight
    prefill_flops = 2.0 * n_params * cost.prompt_tokens
    t_prefill = prefill_flops / (profile.fp16_tflops * 1e12 * profile.mfu)
    t_decode = cost.gen_tokens * recipe.device_bytes / (
        profile.hbm_gbps * GB * profile.bw_eff)
    return t_prefill + t_decode + cost.inference_overhead_s


def task_seconds(profile: DeviceProfile, recipe: ContextRecipe,
                 cost: CostModel, n_items: int) -> float:
    return cost.task_overhead_s + n_items * inference_seconds(
        profile, recipe, cost)
