"""The paper's own workload: MicroBooNE-scale LArTPC signal simulation."""
from repro.config import LArTPCConfig, register


def full() -> LArTPCConfig:
    # 2560 wires x 9592 ticks, 100k depos; the scatter and convolve
    # strategies follow the backend's defaults (lane_rows and direct on a
    # TPU, xla and rfft2 elsewhere)
    return LArTPCConfig(scatter_strategy="auto", fft_strategy="auto")


def smoke() -> LArTPCConfig:
    return LArTPCConfig(num_wires=128, num_ticks=512, num_depos=256,
                        response_wires=11, response_ticks=64)


register("lartpc-uboone", full, smoke)


def full_3p() -> LArTPCConfig:
    # MicroBooNE's TPC readout (arXiv:1612.05824): U and V, 2,400 induction
    # wires each at +-60 degrees, and Y, 3,456 vertical collection wires,
    # all at 3 mm pitch, read at 2 MHz over 4.8 ms. The face the wires
    # cover is the 10,368 mm the Y wires span by the 2,328 mm at which the
    # U and V wires span it (config.face_extent_mm); num_wires is the
    # widest plane
    return LArTPCConfig(
        name="lartpc_uboone3p", num_wires=3456, num_ticks=9600, num_planes=3,
        plane_angles_deg=(60.0, -60.0, 0.0),
        plane_types=("induction", "induction", "collection"),
        plane_wires=(2400, 2400, 3456),
        scatter_strategy="auto", fft_strategy="auto")


def smoke_3p() -> LArTPCConfig:
    return LArTPCConfig(
        name="lartpc_uboone3p", num_wires=128, num_ticks=512,
        num_depos=256, response_wires=11, response_ticks=64, num_planes=3,
        plane_wires=(96, 96, 128))


register("lartpc-uboone3p", full_3p, smoke_3p)
