"""The one Pallas flash backward kernel (PR 48): each live score tile computed
once, dq, dk and dv taken from it (``flash_dkv``; interpret mode on the CPU).

Held to the scan in plain JAX (``_bwd_blockwise``) and to ``jax.grad`` of the
plain ``attend`` at three width pairs, one query head a kv head and four,
causal and not, over 3 q blocks and 3 kv blocks of 128: a q block's dq is
summed over visits that are not consecutive, and a causal grid has dead
steps.  Then the resident accumulator's zeroing at a new (batch, kv head),
with the fault planted to see that the case would catch it, and the
kernel's reach: a rule of the shapes, past which the call takes the scan.

A file that sorts beside ``tests/test_two_widths.py``, late in the order the
suite is handed to its workers (CHANGES.md, PR 45, says why that matters).
"""

import jax
import jax.numpy as jnp
import pytest

from ray_tpu.ops import flash_attention as fa
from ray_tpu.ops.attention import attend

WIDTHS = pytest.mark.parametrize(
    "widths", [(128, 128), (192, 128), (64, 64)], ids=["128", "192-128", "64"])
REPS = pytest.mark.parametrize("reps", [1, 4])
CAUSAL = pytest.mark.parametrize("causal", [True, False])


def _operands(widths, reps, B=1, S=384, KV=1):
    """q, k, v and a cotangent of the output, [B, S, heads, width]."""
    ks = jax.random.split(jax.random.PRNGKey(11), 4)
    d, d_v = widths
    return (jax.random.normal(ks[0], (B, S, KV * reps, d)),
            jax.random.normal(ks[1], (B, S, KV, d)),
            jax.random.normal(ks[2], (B, S, KV, d_v)),
            jax.random.normal(ks[3], (B, S, KV * reps, d_v)))


def _grads(fn, q, k, v, g):
    return jax.jit(jax.grad(lambda *a: (fn(*a) * g).sum(),
                            argnums=(0, 1, 2)))(q, k, v)


def _flash(causal):
    return lambda q, k, v: fa.flash_attention(
        q, k, v, causal=causal, block_q=128, block_kv=128, interpret=True)


def _assert_close(got, want, tol=1e-4):
    for a, b in zip(got, want):
        assert a.shape == b.shape
        assert float(jnp.abs(a - b).max()) < tol * float(jnp.abs(b).max())


@WIDTHS
@REPS
@CAUSAL
def test_fused_backward_matches_the_scan_and_plain_attention(causal, reps,
                                                             widths):
    q, k, v, g = _operands(widths, reps)
    assert fa.flash_bwd_supported(384, reps, 1, widths[0], q.dtype, 128,
                                  128) is None
    got = _grads(_flash(causal), q, k, v, g)
    _assert_close(got, _grads(lambda q, k, v: attend(q, k, v, causal=causal),
                              q, k, v, g))
    # the scan, handed the kernel's own residuals ([B, H, S, D] inside)
    t = [a.swapaxes(1, 2) for a in (q, k, v, g)]
    out, lse = fa._flash_fwd(*t[:3], causal, 128, 128, True)
    scan = fa._bwd_blockwise(*t[:3], out, lse, t[3], causal, 128)
    _assert_close(got, [a.swapaxes(1, 2) for a in scan])


def test_fused_backward_is_one_kernel_of_three_results():
    """One ``pallas_call`` in the backward, named ``flash_dkv``, whose
    results are dq by query head and dk, dv by kv head."""
    q, k, v, g = _operands((192, 128), 4, B=2, KV=2)
    t = [a.swapaxes(1, 2) for a in (q, k, v, g)]
    out, lse = fa._flash_fwd(*t[:3], True, 128, 128, True)
    jaxpr = jax.make_jaxpr(lambda *a: fa._flash_bwd_pallas(
        *a, True, 128, 128, True))(*t[:3], out, lse, t[3])
    calls = [e for e in jaxpr.eqns if e.primitive.name == "pallas_call"]
    assert len(calls) == 1 and "flash_dkv" in str(calls[0])
    assert [o.aval.shape for o in jaxpr.jaxpr.outvars] == [
        (2, 8, 384, 192), (2, 2, 384, 192), (2, 2, 384, 128)]


@pytest.mark.parametrize("fault", [False, True], ids=["sound", "planted"])
def test_the_resident_dq_is_zeroed_at_a_new_batch_row_and_kv_head(
        monkeypatch, fault):
    """Batch 2 and 2 kv heads: the accumulator a (batch, kv head) leaves
    behind is the next one's start unless the kernel zeroes it (and the
    first one's start is what the scratch held: NaN from the interpreter,
    the last call's rows on the chip).  With the zeroing taken out (the
    planted fault) dk and dv still read right and dq wrong: the case sees
    the fault."""
    if fault:
        when = fa.pl.when

        def without_the_zeroing(cond):
            return lambda fn: (fn if fn.__name__ == "_init_dq"
                               else when(cond)(fn))

        monkeypatch.setattr(fa.pl, "when", without_the_zeroing)
    q, k, v, g = _operands((64, 64), 2, B=2, KV=2)
    got = _grads(_flash(True), q, k, v, g)
    want = _grads(lambda q, k, v: attend(q, k, v, causal=True), q, k, v, g)
    _assert_close(got[1:], want[1:])            # dk, dv: their own scratch
    later = (slice(None), slice(None), slice(2, 4))     # kv head 1's queries
    if fault:
        with pytest.raises(AssertionError):
            _assert_close([got[0][later]], [want[0][later]])
    else:
        _assert_close([got[0]], [want[0]])


def test_the_reach_is_a_rule_of_the_shapes():
    """Both train cells sit inside the kernel's reach; a group of four at
    32,768 positions does not, nor do blocks that are no whole 128s."""
    share = (8192, 16, 16, 192, jnp.bfloat16)       # train-moe-share-s8192
    fsdp4 = (4096, 32, 8, 128, jnp.bfloat16)        # train-fsdp4-s4096
    for cell in (share, fsdp4):
        assert fa.flash_bwd_supported(*cell) is None
        assert fa._bwd_resident(cell[0], cell[1] // cell[2], cell[3],
                                cell[4]) == 16 << 20
    assert "reach" in fa.flash_bwd_supported(32768, 32, 8, 128, jnp.bfloat16)
    assert "128" in fa.flash_bwd_supported(64, 4, 4, 64, jnp.float32, 32, 32)
    # what the call asks of the compiler: nothing where the default holds
    # it, else the resident rows and the room of its blocks and products
    assert fa._bwd_vmem(2048, 1, 128, jnp.bfloat16) == {}
    asked = fa._bwd_vmem(8192, 1, 192, jnp.bfloat16)["compiler_params"]
    assert asked.vmem_limit_bytes == (16 << 20) + fa.BWD_VMEM_BLOCKS


def test_past_its_reach_the_backward_is_the_scan_with_the_same_numbers(
        monkeypatch):
    q, k, v, g = _operands((128, 128), 4)
    inside = _grads(_flash(True), q, k, v, g)
    resident = fa._bwd_resident(384, 4, 128, q.dtype)
    monkeypatch.setattr(fa, "BWD_VMEM_REACH", resident - 1)
    assert "reach" in fa.flash_bwd_supported(384, 4, 1, 128, q.dtype, 128, 128)

    def no_kernel(*a, **kw):
        raise AssertionError("the Pallas backward past its reach")

    monkeypatch.setattr(fa, "_flash_bwd_pallas", no_kernel)
    _assert_close(_grads(_flash(True), q, k, v, g), inside)
