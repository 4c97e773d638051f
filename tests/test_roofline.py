"""The roofline's peak table: keyed by device kind, no defaults."""
import importlib.util
import os
import sys

import pytest


@pytest.fixture(scope="module")
def roofline():
    path = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "benchmarks", "roofline.py")
    spec = importlib.util.spec_from_file_location("_bench_roofline", path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[spec.name] = mod        # dataclasses resolve their module
    spec.loader.exec_module(mod)
    return mod


def test_v5e_peaks_published(roofline):
    p = roofline.peaks("TPU v5 lite")
    assert (p.flops, p.hbm_bw) == (197e12, 819e9)


@pytest.mark.parametrize("kind", ["cpu", "TPU v4", ""])
def test_unknown_device_kind_raises(roofline, kind):
    with pytest.raises(KeyError, match="no published peaks"):
        roofline.peaks(kind)


def test_dryrun_device_kind_has_peaks(roofline):
    """The kind the dry-run records for its meshes is in the table."""
    from repro.launch.mesh import PRODUCTION_DEVICE_KIND
    assert roofline.peaks(PRODUCTION_DEVICE_KIND).flops > 0


def test_analyze_needs_record_device_kind(roofline):
    """A dry-run record that names no device kind has no peaks."""
    with pytest.raises(KeyError, match="no published peaks"):
        roofline.analyze({"n_devices": 1, "arch": "x", "shape": "y"},
                         None, None)
