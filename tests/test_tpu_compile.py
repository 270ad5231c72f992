"""The measured kernels compile for a TPU v5e chip (no chip needed).

The TPU compiler is installed with jaxlib, and it compiles for a chip
that is described rather than attached.  Interpret-mode parity cannot
see what this catches: block shapes the (8, 128) tiling refuses, or
kernels that exceed VMEM.  Each case compiles one knob point of one
measured kernel at the geometry ``PallasOracle`` times — the WAMI
stages at their native 128 tile, the fleet kernels at FLASH_S / SSD_S,
and every knob point of fleet-zamba2-7b at its full size —
and checks that a Mosaic kernel (``tpu_custom_call``) is in the
program.

The topology is described inside a fixture, never at import: only one
process at a time may load the TPU library, and every test worker
imports this file.
"""

import jax
import pytest
from jax.sharding import SingleDeviceSharding

WAMI_STAGES = ("debayer", "grayscale", "gradient", "steep_descent",
               "hessian", "warp", "change_det")


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:            # noqa: BLE001
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


def _compile_for_chip(spec, ports, unrolls, sharding):
    program, args = spec.build(ports, unrolls, False)
    shapes = [jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sharding)
              for a in args]
    return program.lower(*shapes).compile()


@pytest.mark.parametrize("ports,unrolls", [(1, 8), (4, 2)],
                         ids=["p1u8", "p4u2"])
@pytest.mark.parametrize("stage", WAMI_STAGES)
def test_wami_stage_compiles_for_v5e(one_chip, stage, ports, unrolls):
    from repro.apps.wami.pallas import wami_pallas_components
    spec = wami_pallas_components(128)[stage]
    compiled = _compile_for_chip(spec, ports, unrolls, one_chip)
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("kernel", ["flash_attention", "ssd_scan"])
def test_fleet_kernel_compiles_for_v5e(one_chip, kernel):
    from repro.apps.fleet.pipeline import fleet_kernel_specs
    spec = fleet_kernel_specs()[kernel]
    compiled = _compile_for_chip(spec, 2, 2, one_chip)
    assert "tpu_custom_call" in compiled.as_text()


def _zamba2_points():
    from repro.apps.fleet import ZAMBA2_7B_TP4, fleet_kernel_specs
    out = []
    for name, spec in fleet_kernel_specs(geometry=ZAMBA2_7B_TP4).items():
        out += [(name, p, u) for p in (1, 2, 4, 8) for u in range(1, 9)
                if spec.divisible(p, u)]
    return out


@pytest.mark.parametrize("kernel,ports,unrolls", _zamba2_points(),
                         ids=[f"{k}-p{p}u{u}" for k, p, u in _zamba2_points()])
def test_zamba2_point_compiles_for_v5e_within_its_vmem(one_chip, kernel,
                                                       ports, unrolls):
    """Every knob point of fleet-zamba2-7b at full size (4096 tokens,
    8 heads of 224; 28 scan heads) compiles inside the scoped VMEM its
    kernel declares."""
    from repro.apps.fleet import ZAMBA2_7B_TP4
    from repro.apps.fleet.pipeline import fleet_input_shapes, fleet_program
    shapes = fleet_input_shapes(ZAMBA2_7B_TP4)
    shapes = shapes[:3] if kernel == "flash_attention" else shapes[3:]
    args = [jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip)
            for a in shapes]
    program = fleet_program(ZAMBA2_7B_TP4, kernel, ports, unrolls, False)
    compiled = program.lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
