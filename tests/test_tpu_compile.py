"""Ahead-of-time compiles of every Pallas kernel for a described TPU v5e.

Interpret mode (what the other kernel tests run on the CPU) accepts
block shapes and VMEM use that Mosaic refuses, so each kernel is also
lowered and compiled here for a ``v5e:2x2`` topology that is described,
not attached.  Shapes are real model widths: smollm-360m attention
(Hq=15, Hkv=5, head_dim=64, 128-token blocks, 4096-token rows),
mamba2-370m SSD chunks and recurrentgemma RG-LRU widths.  Nothing runs,
so these say nothing about results or speed; they catch what the chip's
compiler would refuse before chip time is spent.

The topology is described inside a module fixture (never at import):
only one process may load the TPU library at a time, and every xdist
worker imports this file.
"""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.packed_flash import kernel as K
from repro.kernels.rglru import kernel as RK
from repro.kernels.ssd import kernel as SK

BF16, F32, I32 = jnp.bfloat16, jnp.float32, jnp.int32
HQ, HKV, DH, BLK, SEQ = 15, 5, 64, 128, 4096
T, N, JMAX = 48, 64, 32          # CA tasks, kv blocks, kv blocks per task


@pytest.fixture(scope="module")
def one_chip():
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")
        from jax.experimental import topologies
        try:
            topo = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:   # no TPU compiler in this installation
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


def _flash_fwd(q, k, v, seg, pos):
    return K.flash_fwd(q, k, v, seg, pos, seg, pos, interpret=False,
                       return_lse=True)


def _flash_fwd_sliding(q, k, v, seg, pos):
    return K.flash_fwd(q, k, v, seg, pos, seg, pos, window=256, sink=16,
                       interpret=False)


def _flash_bwd(q, k, v, lse, seg, pos):
    return K.flash_bwd(q, k, v, q, lse, q, seg, pos, seg, pos,
                       interpret=False)


def _ca_fwd(q, k, v, start, length, qp, kp):
    return K.ca_server_fwd(q, k, v, start, length, qp, kp, jmax=JMAX,
                           interpret=False, return_lse=True)


def _ca_fwd_sliding(q, k, v, start, length, qp, kp):
    return K.ca_server_fwd(q, k, v, start, length, qp, kp, jmax=JMAX,
                           window=256, sink=16, interpret=False)


def _ca_bwd(q, k, v, lse, start, length, qp, kp):
    return K.ca_server_bwd(q, k, v, q, lse, q, start, length, qp, kp,
                           jmax=JMAX, interpret=False)


def _decode(q, kc, vc, req, kv_len, qp):
    return K.ragged_decode_fwd(q, kc, vc, req, kv_len, qp, interpret=False)


def _prefill(q, kc, vc, req, kv_len, qp):
    return K.ragged_decode_fwd(q, kc, vc, req, kv_len, qp, window=512,
                               interpret=False)


def _ssd(C_, B_, x, dt, csum, nr):
    return SK.ssd_chunk(C_, B_, x, dt, csum, nr, interpret=False)


def _lru(a, b):
    return RK.lru_scan(a, b, interpret=False)


_FLASH = [((1, SEQ, HQ, DH), BF16), ((1, SEQ, HKV, DH), BF16),
          ((1, SEQ, HKV, DH), BF16)]
_CA = [((T, BLK, HQ, DH), BF16), ((N, BLK, HKV, DH), BF16),
       ((N, BLK, HKV, DH), BF16)]
_CA_META = [((T,), I32), ((T,), I32), ((T, BLK), I32), ((N, BLK), I32)]
_TOKENS = [((1, SEQ), I32), ((1, SEQ), I32)]


def _stacked(shapes, n=2):
    """The same operands with a leading axis of ``n`` simulated servers,
    as ``dispatch._global_sim`` vmaps the CA server over them."""
    return [((n,) + s, d) for s, d in shapes]


CASES = {
    "flash_fwd": (_flash_fwd, _FLASH + _TOKENS),
    "flash_fwd_sliding": (_flash_fwd_sliding, _FLASH + _TOKENS),
    "flash_bwd": (_flash_bwd, _FLASH + [((1, HQ, SEQ), F32)] + _TOKENS),
    "ca_server_fwd": (_ca_fwd, _CA + _CA_META),
    "ca_server_fwd_sliding": (_ca_fwd_sliding, _CA + _CA_META),
    "ca_server_bwd": (_ca_bwd, _CA + [((T, HQ, BLK), F32)] + _CA_META),
    "ca_server_fwd_vmapped": (jax.vmap(_ca_fwd), _stacked(_CA + _CA_META)),
    "ca_server_bwd_vmapped": (
        jax.vmap(_ca_bwd),
        _stacked(_CA + [((T, HQ, BLK), F32)] + _CA_META)),
    "ragged_decode_fwd": (_decode, [
        ((8, 1, HQ, DH), BF16), ((8, SEQ, HKV, DH), BF16),
        ((8, SEQ, HKV, DH), BF16), ((8,), I32), ((8,), I32),
        ((8, 1), I32)]),
    "ragged_prefill_fwd": (_prefill, [
        ((8, BLK, HQ, DH), BF16), ((2, SEQ, HKV, DH), BF16),
        ((2, SEQ, HKV, DH), BF16), ((8,), I32), ((2,), I32),
        ((8, BLK), I32)]),
    # mamba2-370m: chunk 256, d_state 128, head_dim 64, 32 heads
    "ssd_chunk": (_ssd, [
        ((1, 4, 256, 32, 128), F32), ((1, 4, 256, 32, 128), F32),
        ((1, 4, 256, 32, 64), F32), ((1, 4, 256, 32), F32),
        ((1, 4, 256, 32), F32), ((1, 4, 256), I32)]),
    # recurrentgemma-9b: lru_width 4096
    "lru_scan": (_lru, [((1, SEQ, 4096), F32), ((1, SEQ, 4096), F32)]),
}


@pytest.mark.parametrize("name", sorted(CASES))
def test_kernel_compiles_for_v5e(one_chip, name):
    fn, shapes = CASES[name]
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip)
            for s, d in shapes]
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
