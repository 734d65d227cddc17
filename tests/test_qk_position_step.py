"""Where the training step holds the kernel ``hvd_qk_position``
(``ops/qk_position.py``): lowered for a TPU — from here, with no chip — a
patterned model whose heads fit it calls it under ``hvd_attn_rope`` in the
forward, in the layer's recompute and in the backward, and nothing under
that scope joins halves; at a head width that does not fit, and on the CPU,
the step is the jnp form's.  ``hvd_qk_position_built_total{site, form}``
counts the sites as they are traced."""

import re
from functools import partial

import jax
import jax.numpy as jnp
import optax
import pytest

import horovod_tpu as hvd
from horovod_tpu.metrics import registry
from horovod_tpu.models import transformer as tfm
from horovod_tpu.ops import qk_position
from horovod_tpu.parallel.mesh import create_mesh

# Laguna's mix (a full layer that rotates half of each head with YaRN, then
# windowed ones) and SDAR's (per-head norm, a doubled sequence), tiny but
# for a head of 128 lanes.
LAGUNA = tfm.TransformerConfig(
    vocab_size=128, d_model=32, n_heads=2, d_ff=24, n_layers=4, seq_len=48,
    n_experts=16, top_k=3, dtype=jnp.bfloat16, dropless=True,
    tied_head=False, gated_experts=True, layer_pattern="WE",
    leading_pattern="*D", learned_positions=False, n_kv_heads=1,
    attn_head_dim=128, rope_theta=500000.0, rope_fraction=0.5,
    rope_yarn=(128.0, 16, 32.0, 1.0, 1.4852), attn_window=8, window_heads=3,
    window_rope_theta=10000.0, attn_gate=True, dense_ff=40,
    router_renormalise=True, router_scale=2.5, shared_expert_ff=24,
    expert_buffer_factor=64.0)
SDAR = tfm.TransformerConfig(
    vocab_size=64, d_model=32, n_heads=2, d_ff=12, n_layers=4, seq_len=16,
    n_experts=16, top_k=4, dtype=jnp.bfloat16, dropless=True,
    tied_head=False, gated_experts=True, layer_pattern="*E",
    learned_positions=False, n_kv_heads=1, attn_head_dim=128, rope_theta=1e6,
    router_renormalise=True, head_qk_norm=True, diffusion_block=4,
    expert_buffer_factor=64.0)
# (configuration, attention sites a step traces)
MODELS = {"laguna": (LAGUNA, 2), "sdar": (SDAR, 1)}


def built(site, form):
    return registry().counter("hvd_qk_position_built_total", site=site,
                              form=form).value


def lowered(cfg, platform):
    """The text of ``cfg``'s train step lowered for ``platform``, and how
    often each form of the prologue was traced for it."""
    hvd.init()
    mesh = create_mesh({"dp": 1, "pp": 1, "mp": 1}, devices=jax.devices()[:1])
    par, tx = tfm.ParallelConfig(), optax.adamw(1e-3)
    step, shard = tfm.make_train_step(cfg, par, mesh, tx)
    params = jax.eval_shape(
        lambda key: tfm.init_params(key, cfg, par), jax.random.PRNGKey(0))
    state = jax.eval_shape(tx.init, params)
    batch = tfm.synthetic_batch(jax.random.PRNGKey(1), cfg, 2)
    before = {form: built("gqa", form) for form in ("kernel", "xla")}
    text = step.trace(params, state, *batch).lower(
        lowering_platforms=(platform,)).as_text(debug_info=True)
    return text, {form: built("gqa", form) - n for form, n in before.items()}


def names(text):
    """Every location's name in a lowered text."""
    return re.findall(r'^#loc\d+ = loc\("([^"]*)"', text, re.M)


@pytest.mark.parametrize("model", sorted(MODELS))
def test_step_lowered_for_tpu_calls_the_kernel_under_the_rotations_scope(
        model):
    cfg, sites = MODELS[model]
    text, traced = lowered(cfg, "tpu")
    assert traced == {"kernel": sites, "xla": 0}
    assert qk_position.KERNEL in text
    calls = [n for n in names(text)
             if n.endswith(("jit(forward)", "jit(backward)"))]
    assert calls and all("hvd_attn/hvd_attn_rope/" in n for n in calls)
    passes = {("backward" if n.endswith("jit(backward)") else
               "recompute" if "rematted_computation" in n else "forward")
              for n in calls}
    assert passes == {"forward", "recompute", "backward"}
    under = [n for n in names(text) if "hvd_attn_rope" in n]
    assert not [n for n in under if n.endswith(("/concatenate", "/slice"))]
    # The norm went into the kernel; what carries its name is the sum of
    # the programs' partial sums of the scales' gradient.
    normed = [n for n in under if "hvd_attn_qknorm" in n]
    assert bool(normed) == cfg.head_qk_norm
    assert all(n.endswith(("/reshape", "/reduce_sum", "/convert_element_type"))
               for n in normed)


@pytest.mark.parametrize("model", sorted(MODELS))
def test_step_lowered_for_the_cpu_is_the_jnp_form(model):
    cfg, sites = MODELS[model]
    text, traced = lowered(cfg, "cpu")
    assert traced == {"kernel": sites, "xla": 0}   # the shapes fit ...
    assert "tpu_custom_call" not in text           # ... the platform not
    assert [n for n in names(text)
            if "hvd_attn_rope" in n and n.endswith("/concatenate")]


def test_a_head_that_does_not_tile_the_lanes_takes_the_jnp_form():
    cfg = SDAR._replace(attn_head_dim=96)
    text, traced = lowered(cfg, "tpu")
    assert traced == {"kernel": 0, "xla": 1}
    assert qk_position.KERNEL not in text
    found = names(text)
    assert [n for n in found if "hvd_attn_rope" in n
            and n.endswith("/concatenate")]
    assert [n for n in found if "hvd_attn_qknorm" in n
            and "hvd_attn_rope" not in n]


@pytest.mark.parametrize("normed", (False, True))
def test_off_a_tpu_the_fitting_site_is_the_jnp_form_and_its_pullback(normed):
    """Where the shapes fit and the platform does not, the custom VJP's two
    halves are ``_position`` and its pullback written out: equal to AD of
    ``_position``, bit for bit forward, to fp32 rounding backward."""
    keys = jax.random.split(jax.random.PRNGKey(3), 5)
    q, k = (jax.random.normal(key, (2, 32, h, 128), jnp.float32)
            for key, h in zip(keys, (2, 1)))
    scales = tuple(1.0 + 0.2 * jax.random.normal(key, (128,))
                   for key in keys[2:4]) if normed else ()
    angles = partial(tfm._rope_angles, jnp.arange(32), 64, 5e5,
                     LAGUNA.rope_yarn)

    def loss(fn):
        def of(q, k, scales):
            a, b = fn(q, k, scales)
            return jnp.sum(jnp.sin(a)) + jnp.sum(b * b)
        return jax.jit(jax.value_and_grad(of, argnums=(0, 1, 2)))

    before = built("gqa", "kernel")
    got = loss(lambda q, k, s: tfm._position_heads(
        "gqa", q, k, angles, 32, s, 1e-6))(q, k, scales)
    assert built("gqa", "kernel") == before + 1
    want = loss(lambda q, k, s: tfm._position(q, k, *angles(), s, 1e-6))(
        q, k, scales)
    assert got[0] == want[0]
    for a, b in zip(jax.tree_util.tree_leaves(got[1]),
                    jax.tree_util.tree_leaves(want[1])):
        assert jnp.allclose(a, b, rtol=1e-5, atol=1e-5)
