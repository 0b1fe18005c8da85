"""What `auto` resolves to, and why — the trace-time dispatch tables of
training/prefill attention and paged decode, with the backend faked so
the TPU arms are covered on the CPU."""

import jax
import pytest

from areal_tpu.base.topology import MeshSpec
from areal_tpu.engine import paged
from areal_tpu.ops import attention as A
from areal_tpu.parallel.mesh import make_mesh


@pytest.fixture
def on_tpu(monkeypatch):
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")


def test_attn_auto_off_tpu_is_reference_and_says_why():
    assert A._choose_attn_impl("auto", 1024, 12, 2, None, None) == (
        "reference", "backend is cpu, not tpu")


@pytest.mark.parametrize("t,ran", [(1024, "splash"), (128, "splash"),
                                   (64, "reference"), (200, "reference")])
def test_attn_auto_on_tpu_needs_lane_aligned_rows(on_tpu, t, ran):
    got, why = A._choose_attn_impl("auto", t, 12, 2, None, None)
    assert got == ran
    assert "128" in why  # the reason names the alignment either way


def test_attn_on_a_sharded_mesh_needs_a_shard_map_layout(on_tpu):
    fsdp2 = make_mesh(MeshSpec.parse("d1f2"), jax.devices()[:2])
    # Rows split over fsdp=2: splash has a layout ...
    assert A._choose_attn_impl("auto", 1024, 12, 2, fsdp2, 4)[0] == "splash"
    # ... but not for an odd row count: that runs the partitionable
    # reference, and the reason says so.
    ran, why = A._choose_attn_impl("auto", 1024, 12, 2, fsdp2, 3)
    assert ran == "reference" and "no shard_map layout" in why
    # Explicit requests pass through on one device ...
    assert A._choose_attn_impl("splash", 1024, 12, 2, None, None) == ("splash", "requested")
    # ... and a name that is no implementation's (the kernel that left with
    # PR 46, one no one ever wrote) is refused with the names that are, on
    # one device and on the mesh, where it used to run the reference.
    for impl in ("flash", "blocked"):
        for mesh, r in ((None, None), (fsdp2, 4)):
            with pytest.raises(ValueError, match="auto, splash, reference, ring, ulysses"):
                A._choose_attn_impl(impl, 1024, 12, 2, mesh, r)


def test_paged_decode_auto_off_tpu_is_the_gather_path():
    assert paged._choose_paged_decode_impl(False, 128, 128, 8, True)[0] == "xla"


@pytest.mark.parametrize("quantized,page,hd,tp_ok,ran", [
    (False, 128, 128, True, "kernel"),
    (True, 128, 128, True, "int8_kernel"),
    (True, 64, 128, True, "xla"),       # int8 kernel wants 128-aligned pages
    (False, 128, 64, True, "xla"),      # lanes not aligned
    (False, 128, 128, False, "xla"),    # heads do not divide the tensor axis
])
def test_paged_decode_auto_on_tpu(on_tpu, quantized, page, hd, tp_ok, ran):
    got, why = paged._choose_paged_decode_impl(quantized, page, hd, 8, tp_ok)
    assert got == ran and why
