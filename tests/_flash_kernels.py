"""How often a program calls each flash kernel and on what grids, read from
its jaxpr, what the tile counter holds, and the two-kernel backward the one
pass replaced, kept as its oracle.

Shared via ``import _flash_kernels`` (as ``_loadprobe`` is) by the tests of
what the layer checkpoint keeps: tests/test_flash_attention.py,
test_transformer.py, test_bert.py; the oracle and ``bwd_operands`` by
test_flash_backward_pass.py and test_flash_attention_window.py.
"""

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from horovod_tpu.ops import flash_attention as fa

KERNELS = ("hvd_flash_fwd", "hvd_flash_bwd_dq", "hvd_flash_bwd_dkv")
ONCE = dict.fromkeys(KERNELS, 1)


def force_tile(monkeypatch, bq, bk):
    """Every entry point's own tiling, forced: the chooser (``_supported``)
    answers (bq, bk) wherever it answers at all.  ``flash_attention`` alone
    takes ``block_q=`` / ``block_k=``; ``flash_attention_with_lse`` and the
    ring path ask the chooser."""
    real = fa._supported
    monkeypatch.setattr(fa, "_supported",
                        lambda *a, **kw: real(*a, **kw) and (bq, bk))


def kernel_calls(jaxpr) -> dict:
    """``pallas_call`` equations by kernel ``name=`` in a jaxpr's text.  A
    scan's body is printed once, so a layer's calls count once whatever the
    depth; no kernel's name starts another's."""
    text = str(jaxpr)
    return {k: text.count(f"name={k}") for k in KERNELS}


def pallas_grids(fn, *args) -> dict:
    """{kernel name: (grid, number of scalar-prefetch operands)} of every
    pallas_call in ``fn``'s jaxpr (nothing runs)."""
    found = {}

    def walk(jp):
        for eqn in jp.eqns:
            if eqn.primitive.name == "pallas_call":
                gm = eqn.params["grid_mapping"]
                found[eqn.params["name"]] = (tuple(gm.grid),
                                             gm.num_index_operands)
            for sub in jax.core.jaxprs_in_params(eqn.params):
                walk(sub)

    walk(jax.make_jaxpr(fn)(*args).jaxpr)
    return found


def tiles_built(*kernels) -> dict:
    """{(kernel, state): ``hvd_flash_tiles_built_total``} as it stands."""
    from horovod_tpu.metrics import registry
    return {(kernel, state): registry().counter(
        "hvd_flash_tiles_built_total", kernel=kernel, state=state).value
        for kernel in kernels for state in ("live", "skipped", "unvisited")}


def bwd_operands(sq, sk, d, dtype, causal, q_offset=0, kv_offset=0,
                 window=None, b=1, h=2, seed=0):
    """(``fa._bwd_call``'s positional arguments, (B, H, S, D), with ``lse``
    and ``delta`` of the XLA path's forward on the same seeded inputs; the
    ``causal`` and ``scale`` keywords, and the offsets as the Python ints the
    pass lays its walk out from, as the public entry points hand them on)."""
    keys = jax.random.split(jax.random.PRNGKey(seed), 4)
    q, g = (jax.random.normal(key, (b, sq, h, d), dtype)
            for key in keys[::3])
    k, v = (jax.random.normal(key, (b, sk, h, d), dtype)
            for key in keys[1:3])
    scale = 1.0 / d ** 0.5
    out, lse = fa._xla_attention_with_lse(q, k, v, causal, scale, q_offset,
                                          kv_offset, window)
    delta = jnp.sum(g.astype(jnp.float32) * out.astype(jnp.float32),
                    axis=-1).transpose(0, 2, 1)
    offsets = jnp.asarray([[q_offset, kv_offset]], jnp.int32)
    return ([x.transpose(0, 2, 1, 3) for x in (q, k, v, g)]
            + [lse, delta, offsets]), dict(
                causal=causal, scale=scale,
                static_offsets=(q_offset, kv_offset))


# ---------------------------------------------------------------------------
# The backward as it was until PR 34: dQ with the queries resident and the
# keys streamed, dK/dV with the keys resident and the queries streamed, each
# remaking S', P', dP' and dS' of every tile.  Seven dots and two ``exp``
# passes a tile where ``fa._bwd_call`` runs five and one; for fp32 callers the
# pass's three gradients equal these bit for bit in the interpreter (a query
# tile's dQ is summed over ascending key tiles in both).
# ---------------------------------------------------------------------------

def _two_kernel_body(which, off_ref, q_ref, k_ref, v_ref, do_ref, lse_ref,
                     delta_ref, *refs, causal, scale, block_q, block_k,
                     window=None):
    # Every tile of the rectangle is a step, the streamed tile the step's own
    # index, and the body skips the dead ones: the walk the kernels had
    # before their grids followed the mask.
    i, j = pl.program_id(2), pl.program_id(3)       # resident tile, step
    last = pl.num_programs(3) - 1
    dq = which == "dq"
    out_refs, scratch = (refs[:1], refs[1:]) if dq else (refs[:2], refs[2:])
    qt, kt = (i, j) if dq else (j, i)

    @pl.when(j == 0)
    def _init():
        for scr in scratch:
            scr[:] = jnp.zeros_like(scr)

    q_start = off_ref[0, 0] + qt * block_q
    k_start = off_ref[0, 1] + kt * block_k

    live = True
    if causal:
        live = q_start + block_q - 1 >= k_start
    if window is not None:
        live = jnp.logical_and(live, k_start + block_k - 1 > q_start - window)

    @pl.when(live)
    def _compute():
        q, k, v, do = q_ref[0, 0], k_ref[0, 0], v_ref[0, 0], do_ref[0, 0]
        lse = lse_ref[0, 0][:1, :]
        delta = delta_ref[0, 0][:1, :]
        st = fa._scores_t(q, k, causal=causal, scale=scale, q_start=q_start,
                          k_start=k_start, window=window)
        pt = jnp.where(jnp.logical_or(st <= fa._NEG_INF / 2,
                                      lse <= fa._NEG_INF / 2),
                       0.0, jnp.exp(st - lse))
        dpt = jax.lax.dot_general(
            v, do, dimension_numbers=(((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        dst = pt * (dpt - delta) * scale
        if dq:
            scratch[0][:] = scratch[0][:] + jax.lax.dot_general(
                k, dst.astype(k.dtype),
                dimension_numbers=(((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)            # (D, bq)
            return
        dk_scr, dv_scr = scratch
        dv_scr[:] = dv_scr[:] + jax.lax.dot_general(
            pt.astype(do.dtype), do,
            dimension_numbers=(((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        dk_scr[:] = dk_scr[:] + jax.lax.dot_general(
            dst.astype(q.dtype), q,
            dimension_numbers=(((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(j == last)
    def _finalize():
        if dq:
            out_refs[0][0, 0] = jnp.transpose(scratch[0][:]).astype(
                out_refs[0].dtype)
        else:
            for ref, scr in zip(out_refs, scratch):
                ref[0, 0] = scr[:].astype(ref.dtype)


def two_kernel_bwd_call(q_bhsd, k_bhsd, v_bhsd, do_bhsd, lse, delta, offsets,
                        *, causal, scale, block_q, block_k, interpret,
                        window=None, static_offsets=None):
    """``fa._bwd_call``'s arguments and results, by the two old kernels
    (``static_offsets`` lays out the pass's list; this walk has none)."""
    b, h, sq, d = q_bhsd.shape
    sk = k_bhsd.shape[2]
    nq, nk = sq // block_q, sk // block_k
    lse = jnp.broadcast_to(lse[:, :, None, :], (b, h, 8, sq))
    delta = jnp.broadcast_to(delta[:, :, None, :], (b, h, 8, sq))
    off = pl.BlockSpec((1, 2), lambda b, h, i, j: (0, 0),
                       memory_space=pltpu.SMEM)

    def spec(block, tile):
        if block == "row":
            return pl.BlockSpec((1, 1, 8, block_q),
                                lambda b, h, i, j: (b, h, 0, tile(i, j)))
        return pl.BlockSpec((1, 1, block, d),
                            lambda b, h, i, j: (b, h, tile(i, j), 0))

    def call(which, n_res, n_str, keys_streamed, out_lens, scratch):
        resident, streamed = (lambda i, j: i), (lambda i, j: j)
        qi, ki = ((resident, streamed) if keys_streamed
                  else (streamed, resident))
        kern = functools.partial(_two_kernel_body, which, causal=causal,
                                 scale=scale, block_q=block_q,
                                 block_k=block_k, window=window)
        return pl.pallas_call(
            kern, grid=(b, h, n_res, n_str),
            in_specs=[off, spec(block_q, qi), spec(block_k, ki),
                      spec(block_k, ki), spec(block_q, qi),
                      spec("row", qi), spec("row", qi)],
            out_specs=[spec(block_q if keys_streamed else block_k, resident)
                       for _ in out_lens],
            out_shape=[jax.ShapeDtypeStruct((b, h, n, d), q_bhsd.dtype)
                       for n in out_lens],
            scratch_shapes=[pltpu.VMEM(s, jnp.float32) for s in scratch],
            interpret=interpret,
        )(offsets, q_bhsd, k_bhsd, v_bhsd, do_bhsd, lse, delta)

    dq, = call("dq", nq, nk, True, [sq], [(d, block_q)])
    dk, dv = call("dkv", nk, nq, False, [sk, sk], [(block_k, d)] * 2)
    return dq, dk, dv
