"""The paper's own workload: MicroBooNE-scale LArTPC signal simulation."""
from repro.config import LArTPCConfig, register


def full() -> LArTPCConfig:
    # 2560 wires x 9592 ticks, 100k depos; the scatter strategy follows the
    # backend's default (lane_rows on a TPU, xla elsewhere)
    return LArTPCConfig(scatter_strategy="auto")


def smoke() -> LArTPCConfig:
    return LArTPCConfig(num_wires=128, num_ticks=512, num_depos=256,
                        response_wires=11, response_ticks=64)


register("lartpc-uboone", full, smoke)
