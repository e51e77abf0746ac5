"""The one-chip whole-expert dispatch (``models/mixtral.py``
``moe_dispatch``) pads its sorted picks behind the last group up to
``padded_picks`` rows and selects the padding away. On the chip
``ragged_dot`` leaves the rows past its groups UNINITIALISED (NaN among
them, PERF.md Findings PR 28); the CPU zeroes them, so here
``_expert_ffn`` is wrapped to hand them back as NaN. Toy widths,
float32, no engine."""

import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from vllm_distributed_tpu.models.mixtral import (MixtralForCausalLM,
                                                 padded_picks)

H, I = 32, 16
# (experts, picks a token, activation, a pick that is no expert)
FAMILIES = {
    "mixtral": (8, 2, "silu", False),
    "olmoe": (64, 8, "silu", False),
    "zaya": (16, 1, "silu", True),
    "smallthinker": (64, 6, "relu", False),
}


def both_sides_of_every_step(E: int, k: int) -> list[int]:
    """The token counts at which ``padded_picks`` first leaves the picks
    as they are and first takes each row tile (128, 256, 512) for ``k``
    picks a token over ``E`` experts, each with the count before it, and
    the smallest and largest programs' (16 and 2 048 tokens plus the
    attention kernel's 8)."""
    seen, out = set(), {24, 2056}
    for T in range(1, 2057):
        rows = padded_picks(T * k, E)
        step = 0 if rows == T * k else min(rows & -rows, 512)
        if step not in seen:
            seen.add(step)
            out.update({max(T - 1, 1), T})
    return sorted(out)


def toy_model(E: int, k: int, act: str):
    model = object.__new__(MixtralForCausalLM)
    model.cfg = types.SimpleNamespace(
        num_experts=E, num_experts_per_tok=k, num_physical_experts=None,
        expert_parallel=False, experts_held=None, hidden_act=act,
        dtype=jnp.float32)
    keys = jax.random.split(jax.random.PRNGKey(E * 31 + k), 3)
    lp = {"w_gate": jax.random.normal(keys[0], (E, H, I)) * 0.3,
          "w_up": jax.random.normal(keys[1], (E, H, I)) * 0.3,
          "w_down": jax.random.normal(keys[2], (E, I, H)) * 0.3}
    return model, lp


def nan_past_the_groups(ffn):
    def wrapped(lp, xs, group_sizes):
        y = ffn(lp, xs, group_sizes)
        in_group = jnp.arange(xs.shape[0]) < group_sizes.sum()
        return jnp.where(in_group[:, None], y, jnp.nan)
    return wrapped


CASES = [pytest.param(name, T, id=f"{name}-{T}")
         for name, (E, k, _, _) in FAMILIES.items()
         for T in both_sides_of_every_step(E, k)]


@pytest.mark.parametrize("name,T", CASES)
def test_padded_dispatch_equals_the_dense_form(name, T, monkeypatch):
    E, k, act, skip = FAMILIES[name]
    model, lp = toy_model(E, k, act)
    monkeypatch.setattr(model, "_expert_ffn",
                        nan_past_the_groups(model._expert_ffn))
    rng = np.random.default_rng(T * 7 + E)
    x = jnp.asarray(rng.normal(size=(T, H)), jnp.float32)
    # Distinct picks a token, over E outcomes and (ZAYA) one more that
    # is no expert; a skewed router, so some groups are empty.
    p = 1.0 / np.arange(1, E + skip + 1)
    picks = np.stack([rng.choice(E + skip, size=k, replace=False,
                                 p=p / p.sum()) for _ in range(T)])
    top_idx = jnp.asarray(picks, jnp.int32)
    top_vals = jnp.asarray(rng.uniform(0.1, 1.0, size=(T, k)), jnp.float32)
    with jax.default_matmul_precision("highest"):
        got = np.asarray(jax.jit(model.moe_dispatch)(lp, x, top_idx,
                                                     top_vals))
        want = np.asarray(model._moe_dense(lp, x, top_idx, top_vals))
    assert not np.isnan(got).any()
    np.testing.assert_allclose(got, want, atol=2e-5, rtol=2e-5)
    if skip:
        assert np.abs(got[(picks == E)[:, 0]]).max(initial=0.0) == 0.0


@pytest.mark.parametrize("E,k,decode", [(64, 8, 72), (16, 1, 72),
                                        (64, 6, 40)])
def test_the_rule_at_the_cells_token_counts(E, k, decode):
    """Never fewer rows than the picks; the 2 048-token bucket's land on
    whole tiles of 256 rows where ``2 056 x k`` has tiles of 8, 16 or
    64; OLMoE's and SmallThinker's decode batches stay as they are (a
    tile a group would multiply rows it throws away) and ZAYA's 72 rows
    become one tile of 128."""
    for T in (24, 40, 72, 136, 264, 520, 1032, 2056):
        assert padded_picks(T * k, E) >= T * k
    assert padded_picks(2056 * k, E) % 256 == 0
    assert padded_picks(2056 * k, E) - 2056 * k < 256
    assert padded_picks(decode * k, E) == (128 if k == 1 else decode * k)
