"""A sparse-expert feed-forward layer of which this chip holds a share.

The published layer routes every token to the ``top_k`` largest of
``num_experts`` softmax probabilities, renormalises those ``top_k`` and sums
``w_e * W_down^e (silu(W_gate^e u) * W_up^e u)`` over them. A deployment
shares the experts of a layer among chips: this chip holds experts
``held = (lo, hi)`` and computes the terms of the chosen experts it holds;
the other terms belong to other chips and are left out here (no stand-in for
the exchange). The router always looks at all ``num_experts``.

No token is ever dropped and there is no capacity factor: the assignments
that land on held experts are sorted by expert (a stable sort, so tokens keep
their order inside an expert), their rows are gathered into one ragged batch,
and the three expert products are grouped matrix products over it
(``jax.lax.ragged_dot``: XLA:TPU lowers it to a Mosaic grouped-matmul kernel
that visits only the row tiles some group owns, forward and both transposes;
see PERF.md for why not a kernel of our own).

How many assignments land here is data. The worst case is all ``N * top_k``
of them; the expected number is ``N * top_k * (hi - lo) / num_experts``. The
ragged batch is therefore processed in chunks of ``chunk_rows`` sorted rows
under ``lax.cond``: a balanced router runs one chunk, an unbalanced one runs
as many as it needs, and memory is one chunk's whatever the imbalance.

Rows are dispatched by one gather (the sorted rows' token index) and
combined by one scatter-add onto their tokens. On the v5e the scatter-add of
16,384 rows of 2,048 floats takes 1.95 ms where the gather-only form (eight
gathers of 8,192 rows through the inverse permutation, most of them of
assignments held elsewhere) took 3.43 ms and a second sort (my chip run, PR
26). The backward pass is written the same way (``jax.custom_vjp``: autodiff
would turn the dispatch gather into a scatter and the combine into a gather
on its own), recomputing a chunk's hidden activations from its gathered
rows, so the layer keeps no residual but its inputs. Under ``vmap`` both
passes run one mapped element at a time.
"""

from __future__ import annotations

import functools
import typing as t

import jax
import jax.numpy as jnp


class Plan(t.NamedTuple):
    """Integer bookkeeping of one routing decision (carries no gradient)."""

    order: jax.Array  # (N*K,) flat assignment index n*K+k, sorted by held expert
    held: jax.Array   # (N, K) bool: the assignment's expert is held here
    starts: jax.Array  # (E_held,) first sorted row of each held expert
    sizes: jax.Array  # (E_held,) rows of each held expert
    n_rows: jax.Array  # () rows that landed on held experts


def route(u: jax.Array, w_router: jax.Array, top_k: int):
    """``(top_e, top_w)``: each token's ``top_k`` experts of all and their
    renormalised softmax weights. The router's product runs at ``highest``
    precision: the choice is discrete, and a near-tie must flip only on what
    came in, never on this product's own rounding."""
    logits = jnp.dot(
        u.astype(jnp.float32), w_router.astype(jnp.float32),
        precision=jax.lax.Precision.HIGHEST,
    )
    p = jax.nn.softmax(logits, axis=-1)
    _, top_e = jax.lax.top_k(jax.lax.stop_gradient(p), top_k)
    # The chosen probabilities by a mask, not by top_k's own values or a
    # gather: either one's gradient is a scatter of N * top_k scalars.
    chosen = top_e[:, :, None] == jnp.arange(p.shape[-1], dtype=top_e.dtype)
    top_p = jnp.sum(jnp.where(chosen, p[:, None, :], 0.0), axis=-1)
    return top_e, top_p / jnp.sum(top_p, axis=-1, keepdims=True)


def plan_assignments(top_e: jax.Array, held: t.Tuple[int, int]) -> Plan:
    lo, hi = held
    n_held = hi - lo
    n, k = top_e.shape
    flat = top_e.reshape(-1).astype(jnp.int32) - lo
    is_held = (flat >= 0) & (flat < n_held)
    key = jnp.where(is_held, flat, n_held)
    order = jnp.argsort(key, stable=True).astype(jnp.int32)
    sizes = jnp.sum(
        key[:, None] == jnp.arange(n_held, dtype=jnp.int32)[None, :], axis=0,
        dtype=jnp.int32,
    )
    return Plan(
        order=order, held=is_held.reshape(n, k),
        starts=jnp.cumsum(sizes) - sizes, sizes=sizes, n_rows=jnp.sum(sizes),
    )


def default_chunk_rows(n_tokens: int, top_k: int, n_held: int, n_experts: int) -> int:
    """Twice the expected number of held assignments, in whole 512-row tiles,
    at most all of them."""
    expected = n_tokens * top_k * n_held / n_experts
    rows = -(-int(2 * expected) // 512) * 512
    return max(min(rows, n_tokens * top_k), 1)


# The transposed grouped product: contract the ragged rows, one (k, n) block
# a group.
_BY_GROUP = jax.lax.RaggedDotDimensionNumbers(
    dot_dimension_numbers=(((0,), (0,)), ((), ())),
    lhs_ragged_dimensions=[0], rhs_group_dimensions=[],
)


def _mxu(x, bf16_dots: bool):
    """An operand as the TPU's default precision takes a float32 one: rounded
    to bfloat16 (``bf16_dots``, the attention kernels' switch of the same
    name; a configuration states it, ``SACConfig.trunk_bf16_dots``, and it
    means the same on every platform). On the TPU the grouped-product kernel
    rounds float32 operands the same way itself (same result to the bit and
    the same time alone; my chip run, PR 26), but handed bfloat16 it reads
    half the bytes, and inside the burst the grouped products went from 41.6
    to 28-31 ms a step."""
    if bf16_dots and x.dtype == jnp.float32:
        return x.astype(jnp.bfloat16)
    return x


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _gmm(x, w, sizes, bf16_dots):
    """``x`` ``(rows, k)`` sorted by group times ``w`` ``(groups, k, n)``."""
    return jax.lax.ragged_dot(
        _mxu(x, bf16_dots), _mxu(w, bf16_dots), sizes,
        preferred_element_type=jnp.float32,
    )


def _gmm_fwd(x, w, sizes, bf16_dots):
    return _gmm(x, w, sizes, bf16_dots), (x, w, sizes)


def _gmm_bwd(bf16_dots, res, g):
    x, w, sizes = res
    g = _mxu(g, bf16_dots)
    dx = jax.lax.ragged_dot(
        g, _mxu(jnp.swapaxes(w, 1, 2), bf16_dots), sizes,
        preferred_element_type=jnp.float32,
    )
    dw = jax.lax.ragged_dot_general(
        _mxu(x, bf16_dots), g, sizes, _BY_GROUP, preferred_element_type=jnp.float32
    )
    return dx.astype(x.dtype), dw.astype(w.dtype), None


_gmm.defvjp(_gmm_fwd, _gmm_bwd)


def _core(xs, w_gate, w_up, w_down, sizes, bf16_dots):
    """The expert network on a ragged batch sorted by expert."""
    hidden = jax.nn.silu(_gmm(xs, w_gate, sizes, bf16_dots)) * _gmm(
        xs, w_up, sizes, bf16_dots
    )
    return _gmm(hidden, w_down, sizes, bf16_dots)


class _Chunk(t.NamedTuple):
    flat: jax.Array   # (R,) flat assignment index n*K+k of each sorted row
    tok: jax.Array    # (R,) its token
    w: jax.Array      # (R,) its routing weight, 0 past the held rows
    live: jax.Array   # (R, 1) whether the row is a held assignment at all
    sizes: jax.Array  # (E_held,) rows of each expert inside the chunk


def _chunk(plan: Plan, top_w, c, rows: int) -> _Chunk:
    k = top_w.shape[1]
    r0 = c * rows
    flat = jax.lax.dynamic_slice(plan.order, (r0,), (rows,))
    live = r0 + jnp.arange(rows, dtype=jnp.int32) < plan.n_rows
    w = jnp.where(live, jnp.take(top_w.reshape(-1), flat), 0.0)
    ends = jnp.minimum(plan.starts + plan.sizes, r0 + rows)
    sizes = jnp.maximum(ends - jnp.maximum(plan.starts, r0), 0)
    return _Chunk(flat, flat // k, w, live[:, None], sizes)


def _over_chunks(plan: Plan, rows: int, total: int, body, init):
    """``body(c, carry)`` for every chunk that holds a held row."""
    n_chunks = -(-total // rows)
    if n_chunks == 1:
        return body(0, init)

    def step(c, carry):
        return jax.lax.cond(c * rows < plan.n_rows, lambda x: body(c, x), lambda x: x, carry)

    return jax.lax.fori_loop(0, n_chunks, step, init)


def _padded(plan: Plan, rows: int) -> Plan:
    """``order`` padded so that the last chunk's slice stays in bounds."""
    total = plan.order.shape[0]
    pad = -total % rows
    if not pad:
        return plan
    return plan._replace(order=jnp.pad(plan.order, (0, pad)))


def _forward(rows: int, bf16_dots: bool, u, w_gate, w_up, w_down, top_w, plan: Plan):
    total = top_w.size
    padded = _padded(plan, rows)

    def body(c, out):
        ch = _chunk(padded, top_w, c, rows)
        y = _core(jnp.take(u, ch.tok, axis=0), w_gate, w_up, w_down, ch.sizes, bf16_dots)
        # Rows past the held ones belong to no group: whatever the grouped
        # product left there is cut off, and they add 0 to some token.
        return out.at[ch.tok].add(jnp.where(ch.live, y, 0) * ch.w[:, None])

    return _over_chunks(plan, rows, total, body, jnp.zeros_like(u))


def _backward(rows: int, bf16_dots: bool, u, w_gate, w_up, w_down, top_w, plan: Plan, g):
    total = top_w.size
    padded = _padded(plan, rows)

    def body(c, carry):
        du, dg, dup, ddown, dw = carry
        ch = _chunk(padded, top_w, c, rows)
        y, vjp = jax.vjp(
            lambda xs, a, b, d: _core(xs, a, b, d, ch.sizes, bf16_dots),
            jnp.take(u, ch.tok, axis=0), w_gate, w_up, w_down,
        )
        g_rows = jnp.take(g, ch.tok, axis=0)
        dxs, a, b, d = vjp(jnp.where(ch.live, g_rows * ch.w[:, None], 0))
        # d out / d weight of a row is <y_row, g_row>.
        dw_rows = jnp.sum(jnp.where(ch.live, y * g_rows, 0), axis=-1)
        dw = dw.reshape(-1).at[ch.flat].add(dw_rows).reshape(dw.shape)
        return (
            du.at[ch.tok].add(jnp.where(ch.live, dxs, 0)),
            dg + a, dup + b, ddown + d, dw,
        )

    zeros = jax.tree_util.tree_map(jnp.zeros_like, (u, w_gate, w_up, w_down, top_w))
    return _over_chunks(plan, rows, total, body, zeros)


@functools.lru_cache(maxsize=None)
def _experts_for(rows: int, bf16_dots: bool):
    """The expert layer for chunks of ``rows``, with its hand-written
    backward pass. Under ``vmap`` (the data-parallel burst maps the update
    over its device axis, a population over its members) both passes run
    once a mapped element, in turn (``sequential_vmap``): the grouped product
    has no batched form on the TPU, and a batched ``lax.cond`` would run
    every chunk of every element."""
    forward = jax.custom_batching.sequential_vmap(
        functools.partial(_forward, rows, bf16_dots)
    )
    backward = jax.custom_batching.sequential_vmap(
        functools.partial(_backward, rows, bf16_dots)
    )

    @jax.custom_vjp
    def experts(u, w_gate, w_up, w_down, top_w, plan):
        return forward(u, w_gate, w_up, w_down, top_w, plan)

    def fwd(u, w_gate, w_up, w_down, top_w, plan):
        out = forward(u, w_gate, w_up, w_down, top_w, plan)
        return out, (u, w_gate, w_up, w_down, top_w, plan)

    def bwd(res, g):
        return (*backward(*res, g), None)

    experts.defvjp(fwd, bwd)
    return experts


def expert_ffn(
    u: jax.Array, w_gate: jax.Array, w_up: jax.Array, w_down: jax.Array,
    top_e: jax.Array, top_w: jax.Array, held: t.Tuple[int, int],
    chunk_rows: int | None = None, num_experts: int | None = None,
    bf16_dots: bool = False,
) -> t.Tuple[jax.Array, Plan]:
    """This chip's partial sum of the expert layer for tokens ``u`` ``(N, H)``.

    ``w_gate``/``w_up``: ``(hi - lo, H, F)``, ``w_down``: ``(hi - lo, F, H)``,
    the held experts' kernels. ``top_e``/``top_w``: :func:`route`'s choices
    over all experts. Returns the ``(N, H)`` sum over each token's chosen
    experts that are held, and the :class:`Plan` (its counters).
    ``bf16_dots`` rounds the float32 operands of every grouped product to
    bfloat16 (float32 accumulation and output), forward and backward."""
    n, k = top_e.shape
    n_held = held[1] - held[0]
    if chunk_rows is None:
        chunk_rows = default_chunk_rows(n, k, n_held, num_experts or n_held)
    chunk_rows = min(chunk_rows, n * k)
    plan = plan_assignments(top_e, held)
    top_w = jnp.where(plan.held, top_w, 0.0)  # an absent term has no gradient here
    experts = _experts_for(chunk_rows, bool(bf16_dots))
    return experts(u, w_gate, w_up, w_down, top_w, plan), plan
