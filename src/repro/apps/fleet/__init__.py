"""Fleet: the hybrid attention + SSD pipeline, the second registered
COSMOS app (``get_app("fleet")``), and the same pipeline at Zamba2-7B's
published widths (``get_app("fleet-zamba2-7b")``)."""

from .pipeline import (FLASH_D, FLASH_HEADS, FLASH_S, FLEET, SSD_MAX_HEADS,
                       SSD_N, SSD_P, SSD_S, ZAMBA2_7B_TP4, FleetGeometry,
                       default_measurement_path, fleet_app,
                       fleet_calibrated_tool, fleet_kernel_specs,
                       fleet_knob_spaces, fleet_pallas_oracle,
                       fleet_parity_cases, fleet_session, fleet_tmg,
                       fleet_unit_system, fleet_xla_tool)

__all__ = [
    "FLASH_S", "FLASH_D", "FLASH_HEADS", "SSD_S", "SSD_P", "SSD_N",
    "SSD_MAX_HEADS", "FleetGeometry", "FLEET", "ZAMBA2_7B_TP4",
    "fleet_app", "fleet_tmg", "fleet_knob_spaces",
    "fleet_xla_tool", "fleet_kernel_specs", "fleet_pallas_oracle",
    "fleet_calibrated_tool", "fleet_unit_system", "fleet_session",
    "fleet_parity_cases", "default_measurement_path",
]
