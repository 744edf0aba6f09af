"""A sparse-expert feed-forward layer of which this chip holds a share.

A published layer routes every token to ``top_k`` of ``num_experts`` and sums
its chosen experts' outputs by their weights. Two forms are written here, and
a layer takes its own from its spec (``models/sequence.py::TrunkSpec``).
*Routing* (:func:`route`): the ``top_k`` largest softmax probabilities,
renormalised; or sigmoid scores, chosen by score plus a correction bias,
weighted by the scores alone, renormalised and scaled; the choice itself is
one selection pass (:func:`top_scores`, below). *Expert*
(``FORMS``): the gated ``W_down^e (silu(W_gate^e u) * W_up^e u)`` with three
kernels, or the plain ``W_down^e relu(W_up^e u)^2`` with two. The experts work
in whatever width ``u`` has (a latent one where the layer projects down before
them and up after). Plan, chunks, pieces, dispatch and combine are one code
for every form. A deployment
shares the experts of a layer among chips: this chip holds experts
``held = (lo, hi)`` and computes the terms of the chosen experts it holds;
the other terms belong to other chips and are left out here (no stand-in for
the exchange). The router always looks at all ``num_experts``.

The selection (:func:`top_scores`) takes a token's ``top_k`` of
``num_experts`` scores in descending order, equal scores by the lower index
first: ``lax.top_k``'s answer to the element, which the references' stable
sorts hold it to. It does not sort. ``lax.top_k`` lowers on XLA:TPU to a whole
sort of a token's scores with their indices (22 of 512 over 4,096 tokens:
0.335-0.367 ms a call, fifteen calls a step of the hybrid trunk cell), and
the chosen scores were then picked by a mask over tokens x ``top_k`` x
experts, because a gather's gradient is a scatter of ``N * top_k`` scalars.
A round of the selection has the chosen score in hand where it finds the
index, so choice and scores are one pass and nothing is gathered; the
gradient puts each round's cotangent back at its index by ``top_k``
compare-selects over a tile, no scatter and no mask in memory. On a TPU both
directions are kernels over tiles of ``TOKENS_TILE`` tokens (names
``router_top_k`` / ``router_top_k_bwd`` in a trace); everywhere else, and
for the tests, XLA composes the same rounds. Read on the v5e (my chip runs,
PR 41; ten steps of the hybrid cell's burst, one process, one state): ``top_k``
and the mask 2,265.9 ms; the rounds composed by XLA (each a reduction over
``(N, E)`` that carries score, index and ``p``) with the selects fused into the
router's backward products 2,291.2; the forward kernel with those selects
2,261.5 (XLA fuses the chain into both products and computes it twice, slowly
in ``du``'s; as a kernel's output it is written once); both kernels 2,196.3
with tiles of 256 tokens, 2,205.2 with 128, 2,203.0 with 512. ``route`` alone,
forward / forward and gradient, 4,096 tokens of 4,096 to 512 scores, 22
chosen: 1.012 / 2.188 ms before, 0.945 / 2.482 composed, 0.665 / 1.879 with
the kernels; 8,192 tokens of 2,048 to 128, 8 chosen (SDAR's): 0.496 / 0.865,
0.402 / 0.566, 0.400 / 0.702.

No token is ever dropped and there is no capacity factor: the assignments
that land on held experts are put in order by expert, inside an expert by
flat index ``n * top_k + k`` (so tokens keep their order inside an expert),
their rows are gathered into one ragged batch, and the three expert products
are grouped matrix products over it (``jax.lax.ragged_dot``: XLA:TPU lowers it
to a Mosaic grouped-matmul kernel that visits only the row tiles some group
owns, forward and both transposes; see PERF.md for why not a kernel of our
own).

The order is the plan's (:func:`plan_assignments`), and it orders what this
chip can hold, not every token's every choice. It was a stable ``argsort`` of
one key a choice (the held expert, or one past them for "held elsewhere"):
on XLA:TPU a sort of two operands, key and iota, under a comparator over
both, 90,112 elements in the hybrid trunk cell of which a call holds 1,408,
fifteen calls a step; nothing reads the order past the held rows. Now one
``int32`` word a candidate, expert in the high part and flat index in the
low, so that one operand sorts under the default comparator, equal experts
keep their order by construction and the order is the sorted word modulo
``N * top_k``; the sort is asked for unstable, the words being distinct (asked
for stable, XLA:TPU puts the iota back: sandbox compile, PR 43). A token's
choices are distinct, so where fewer experts are held than a token chooses
the candidates are the pairs (held expert, token), ``N * n_held`` of them
(32,768 in the hybrid cell), the ``k`` that chose the expert found by the
compare that counts the experts' rows and carried in the word; where as many
or more are held (SDAR's 16 of 8 chosen) the candidates are the ``N * top_k``
assignments. Both are one code that reads two static shapes. There a token's
held choices have to be distinct, as :func:`route`'s are: of two choices of
one held expert in one token only the later would get a row.

Every form was tried inside the whole burst on the chip, one process, five
windows of ten steps each, ms a step by the windows' median (v5e, my chip
runs, PR 43): the hybrid trunk cell with the stable ``argsort`` 216.97; one
word a key over every choice 211.79; the candidates counted the shorter way
as well, which is what stands here, 207.49; no sort at all (a row's
assignment found by comparing a running count of the candidates with the
row's number, 2,048 rows at a time, only where rows are held) 208.26, and that
form runs its blocks in a loop that the burst's map over its device axis has
to take one element at a time. In the trace a call's sort went from 0.670 to
0.071 ms, 10.06 to 1.06 ms a step. The SDAR cell, whose candidates are every
choice either way: 188.93 with the ``argsort``, 186.47 with one word a key.

How many assignments land here is data. The worst case is all ``N * top_k``
of them; the expected number is ``N * top_k * (hi - lo) / num_experts``. The
ragged batch is therefore processed in chunks of ``chunk_rows`` sorted rows:
a balanced router runs one chunk, an unbalanced one runs as many as it needs
(the further ones under ``lax.cond``), and memory is one chunk's whatever the
imbalance. A chunk is what the grouped products see, and they skip its dead
tiles themselves. Everything else that costs in proportion to rows (the
dispatch gather, the casts, the silu-multiply, the masks, the combine)
follows the *counted* rows: it runs in pieces of ``PIECE_ROWS`` rows under a
loop whose trip count is the chunk's held rows, so the pieces past them are
never run. At the trunk cell's routing a call holds 6,041 to 9,808 rows where
8,192 are expected, by layer and seed and hardly by batch (the program's
counter over 4 seeds, 4 layers and 6 batches, counted on the CPU; 7,818 to
8,636 on the chip, seed 3700022001): no fixed size sits just over that, which
is why the pieces follow the count and the chunk keeps its margin. Before,
all of it passed over the chunk's 16,384 rows (my chip runs, PR 37, one step
of the cell, microseconds before -> after): scatter-adds 17,740 -> 9,250,
gathers 6,250 -> 2,890, casts, silu-multiply and masks 11,140 -> 4,540, the
zeros and sums of the kernels' gradients 7,240 -> 150, the grouped products
28,990 -> 28,470; the layer alone, forward and backward, 17.03 -> 12.23 ms.

Rows are dispatched by a gather (the sorted rows' token index) and combined
by a scatter-add onto their tokens, a piece at a time and the pieces in
order, so a token's terms are added in the order of its sorted rows. On the
v5e a scatter-add costs 92 ns a row of 2,048 floats whatever its size (1.41 ms
for 16,384 rows, 47 us for 512: my chip runs, PR 37) and a gather of bfloat16
rows 28 ns; the gather-only form (eight gathers of 8,192 rows through the
inverse permutation, most of them of assignments held elsewhere) took 3.43 ms
and a second sort (my chip run, PR 26). Pieces of 512, 1,024 and 2,048 rows
read 12.84, 12.97 and 16.31 ms for the layer (my chip run, PR 37). A buffer a
loop fills piece by piece is not cleared first (``lax.empty``: 2.3 ms a step
of zeros otherwise): a piece is whole tiles of the grouped product, so every
tile the product visits lies in a piece that was written, and rows of no
group do not reach its results (NaN there changes nothing, on the chip and on
the CPU: my chip run, PR 37, and ``tests/test_trunk.py``).

The backward pass is written the same way (``jax.custom_vjp``: autodiff
would turn the dispatch gather into a scatter and the combine into a gather
on its own), recomputing a chunk's hidden activations from its gathered
rows, so the layer keeps no residual but its inputs. The first chunk's
kernel gradients are the gradients; only a further chunk adds to them (a sum
of three whole kernels, 302 MB of float32, 1.1 ms). Both passes stand under
one ``lax.cond`` on there being a held row at all: besides skipping a call
that holds none, it keeps XLA's layout assignment from carrying the
transposed kernel layout the input-gradient products want up into the
burst's state (it cost a float32 relayout of every kernel's gradient every
step and 3.5 GB: sandbox compiles, PR 37). Under ``vmap`` both passes run
one mapped element at a time.
"""

from __future__ import annotations

import functools
import operator
import typing as t

import jax
import jax.numpy as jnp

from torch_actor_critic_tpu.telemetry import scopes


class Plan(t.NamedTuple):
    """Integer bookkeeping of one routing decision (carries no gradient)."""

    order: jax.Array  # (N*min(K, E_held),) flat assignment index n*K+k, sorted by held expert
    held: jax.Array   # (N, K) bool: the assignment's expert is held here
    starts: jax.Array  # (E_held,) first sorted row of each held expert
    sizes: jax.Array  # (E_held,) rows of each held expert
    n_rows: jax.Array  # () rows that landed on held experts


def route(
    u: jax.Array, w_router: jax.Array, top_k: int, scoring: str = "softmax",
    bias: jax.Array | None = None, scale: float = 1.0, impl: str = "auto",
):
    """``(top_e, top_w)``: each token's ``top_k`` experts of all and their
    weights. ``scoring="softmax"``: the largest probabilities, renormalised,
    times ``scale``. ``"sigmoid"``: scores ``s = sigmoid(u W_r)``; the chosen
    are the largest of ``s + bias`` (the correction bias moves the choice
    alone), their weights ``scale * s / (sum of the chosen s + 1e-20)``. A
    softmax router's ``scale`` of 1 multiplies nothing (the program of a stack
    that states none is the one it was). The router's product runs
    at ``highest`` precision: the choice is discrete, and a near-tie must flip
    only on what came in, never on this product's own rounding. The choice
    and the chosen scores are :func:`top_scores`'s (``impl``)."""
    logits = jnp.dot(
        u.astype(jnp.float32), w_router.astype(jnp.float32),
        precision=jax.lax.Precision.HIGHEST,
    )
    if scoring == "softmax":
        p, bias = jax.nn.softmax(logits, axis=-1), None
    else:
        p = jax.nn.sigmoid(logits)
    top_e, top_p = top_scores(p, bias, top_k, impl)
    if scoring == "softmax":
        top_w = top_p / jnp.sum(top_p, axis=-1, keepdims=True)
        return top_e, top_w if scale == 1.0 else scale * top_w
    return top_e, scale * top_p / (jnp.sum(top_p, axis=-1, keepdims=True) + 1e-20)


# Tokens of one tile of the selection's kernels (module docstring: 128 and
# 512 read slower inside the burst).
TOKENS_TILE = 256


def top_scores(p: jax.Array, bias: jax.Array | None, top_k: int, impl: str = "auto"):
    """``(top_e, top_p)``, both ``(N, top_k)``: for each row of the scores
    ``p`` ``(N, E)`` its ``top_k`` largest of ``p + bias`` (of ``p`` without
    one) in descending order, equal scores by the lower index first (what
    ``lax.top_k`` gives, to the element), and ``p`` there. The gradient goes
    to ``p`` at the chosen places; none to ``bias``.

    ``top_k`` rounds of *the largest, the first index that holds it, strike
    it out*; a round reads ``p`` where it chose, so the chosen scores come
    with the choice. ``'xla'`` composes the rounds (each one reduction over
    ``(N, E)``, the gradient a chain of selects), ``'pallas'`` runs them
    over a tile of ``TOKENS_TILE`` tokens resident in VMEM and the gradient
    over such a tile (float32, ``E`` whole lanes), ``'interpret'`` those
    kernels in the Pallas interpreter; ``'auto'`` the kernels on a TPU where
    they have blocks, chosen at trace time like
    :func:`ops.attention.qk_norm_rope`, with its CAUTION (module docstring:
    what each form read on the chip)."""
    if impl == "auto":
        fits = p.dtype == jnp.float32 and p.shape[-1] % 128 == 0
        impl = "pallas" if jax.default_backend() == "tpu" and fits else "xla"
    return _top_scores(p, bias, top_k, p.shape[-1], impl)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3, 4))
def _top_scores(p, bias, top_k, num_experts, impl):
    return _top_scores_fwd(p, bias, top_k, num_experts, impl)[0]


def _top_scores_fwd(p, bias, top_k, num_experts, impl):
    if impl == "xla":
        out = _rounds(p, bias, top_k)
    else:
        out = _rounds_kernel(p, bias, top_k, impl == "interpret")
    return out, (out[0], bias)


def _rounds(p, bias, top_k):
    """The rounds as XLA composes them: a round is one reduction over the
    pairs (score, index) that are left, the larger score and of equal scores
    the lower index, carrying ``p``. What is left is what comes after the
    last choice in that order, so nothing is struck out in memory."""
    n, e = p.shape
    select = p if bias is None else p + bias
    index = jax.lax.broadcasted_iota(jnp.int32, (n, e), 1)
    carried = (select, index) if bias is None else (select, index, p)
    lowest = (-jnp.inf, e, 0.0)[:len(carried)]

    def first_largest(x, y):
        x_wins = (x[0] > y[0]) | ((x[0] == y[0]) & (x[1] < y[1]))
        return tuple(jnp.where(x_wins, a, b) for a, b in zip(x, y))

    top_e, top_p, last = [], [], None
    for _ in range(top_k):
        left = carried
        if last is not None:
            m, i = last
            after = (select < m) | ((select == m) & (index > i))
            left = (jnp.where(after, select, -jnp.inf), *carried[1:])
        m, i, *chosen = jax.lax.reduce(
            left, tuple(jnp.asarray(v, a.dtype) for v, a in zip(lowest, left)),
            first_largest, (1,),
        )
        top_e.append(i)
        top_p.append(chosen[0] if chosen else m)
        last = (m[:, None], i[:, None])
    return jnp.stack(top_e, axis=1), jnp.stack(top_p, axis=1)


def _rounds_kernel_body(p_ref, *refs, top_k: int, biased: bool):
    """One tile of tokens by all experts, turned once so that the tokens lie
    along the lanes and the experts along the sublanes (a reduction over
    experts is then elementwise but for its last eight). A round scans the
    experts eight at a time, in order: it strikes the last round's choice out
    of the scores left in ``s_ref``, and keeps for each sublane and token the
    largest score so far, where it stood and ``p`` there (a strictly larger
    one replaces it, so the first of equal scores stays); eight candidates a
    token are then one sublane reduction each."""
    from jax.experimental import pallas as pl

    if biased:  # choose by p + bias, hand back p: both are kept, turned
        bias_ref, e_ref, w_ref, s_ref, pt_ref = refs
        pt_ref[...] = p_ref[...].T
        s_ref[...] = pt_ref[...] + bias_ref[...]
    else:  # the score chosen by is the score handed back
        (e_ref, w_ref, s_ref), pt_ref = refs, None
        s_ref[...] = p_ref[...].T
    n_experts, tile = s_ref.shape
    sublane = jax.lax.broadcasted_iota(jnp.int32, (8, tile), 0)

    def round_(r, struck):
        best = jnp.full((8, tile), -jnp.inf, jnp.float32)
        slab = jnp.zeros((8, tile), jnp.int32)
        score = jnp.zeros((8, tile), jnp.float32)
        for j in range(n_experts // 8):
            rows = slice(8 * j, 8 * j + 8)
            s = jnp.where(sublane == struck - 8 * j, -jnp.inf, s_ref[rows, :])
            s_ref[rows, :] = s
            larger = s > best
            best = jnp.where(larger, s, best)
            slab = jnp.where(larger, j, slab)
            if pt_ref is not None:
                score = jnp.where(larger, pt_ref[rows, :], score)
        expert = 8 * slab + sublane
        m = jnp.max(best, axis=0, keepdims=True)
        chosen = jnp.min(jnp.where(best == m, expert, n_experts), axis=0, keepdims=True)
        if pt_ref is not None:
            m = jnp.sum(jnp.where(expert == chosen, score, 0.0), axis=0, keepdims=True)
        e_ref[pl.ds(r, 1), :] = chosen
        w_ref[pl.ds(r, 1), :] = m
        return chosen

    jax.lax.fori_loop(0, top_k, round_, jnp.full((1, tile), -1, jnp.int32))


# ``jax.jit`` round a kernel's call: a program that calls it at many places
# (the hybrid burst: twenty) traces and lowers it once a shape (4.8 s of the
# burst's 14.5 s of lowering otherwise: sandbox, PR 41), and XLA inlines the
# calls, each under its call site's scopes.
_traced_once = functools.partial(jax.jit, static_argnums=(2, 3))


@_traced_once
def _rounds_kernel(p, bias, top_k, interpret):
    """:func:`_rounds_kernel_body` over tiles of ``TOKENS_TILE`` tokens, the
    tokens padded to whole tiles. The scores go in as the router's product
    leaves them, tokens first (asked for experts first, XLA lays the product
    itself out that way and turns its 64 MB operand instead: five times the
    product's time by the compiler's own estimate, sandbox compile, PR 41);
    the choices come out rounds first and XLA turns those."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    n, e = p.shape
    tile = min(TOKENS_TILE, -(-n // 128) * 128)
    padded = -(-n // tile) * tile
    vmem = functools.partial(pl.BlockSpec, memory_space=pltpu.VMEM)
    picked = vmem((top_k, tile), lambda i: (0, i))
    operands = [jnp.pad(p, ((0, padded - n), (0, 0)))]
    in_specs = [vmem((tile, e), lambda i: (i, 0))]
    turned = [pltpu.VMEM((e, tile), jnp.float32)]
    if bias is not None:
        operands.append(bias.astype(p.dtype).reshape(e, 1))
        in_specs.append(vmem((e, 1), lambda i: (0, 0)))
        turned = 2 * turned
    top_e, top_p = pl.pallas_call(
        functools.partial(_rounds_kernel_body, top_k=top_k, biased=bias is not None),
        out_shape=[
            jax.ShapeDtypeStruct((top_k, padded), jnp.int32),
            jax.ShapeDtypeStruct((top_k, padded), p.dtype),
        ],
        grid=(padded // tile,),
        in_specs=in_specs,
        out_specs=[picked, picked],
        scratch_shapes=turned,
        compiler_params=pltpu.CompilerParams(dimension_semantics=("parallel",)),
        interpret=interpret,
        name="router_top_k",
    )(*operands)
    return top_e[:, :n].T, top_p[:, :n].T


def _top_scores_bwd(top_k, num_experts, impl, res, g):
    top_e, bias = res
    _, g = g  # the choice is integers
    if impl == "xla":
        dp = _chosen_to_scores(top_e, g, num_experts)
    else:
        dp = _chosen_to_scores_kernel(top_e, g, num_experts, impl == "interpret")
    return dp, None if bias is None else jnp.zeros_like(bias)


def _chosen_to_scores(top_e, g, num_experts):
    """``(N, E)``: ``g`` at the chosen places and zero elsewhere. A token
    chooses an expert once at most: a chain of selects, no sum, no scatter."""
    experts = jnp.arange(num_experts, dtype=top_e.dtype)
    dp = jnp.zeros((top_e.shape[0], num_experts), g.dtype)
    for r in range(top_e.shape[1]):
        dp = jnp.where(top_e[:, r, None] == experts, g[:, r, None], dp)
    return dp


def _chosen_to_scores_body(e_ref, g_ref, dp_ref):
    """:func:`_chosen_to_scores` for one tile of tokens, eight at a time."""
    from jax.experimental import pallas as pl

    experts = jax.lax.broadcasted_iota(jnp.int32, (8, dp_ref.shape[1]), 1)

    def eight(i, carry):
        rows = pl.ds(pl.multiple_of(8 * i, 8), 8)
        top_e, g = e_ref[rows, :], g_ref[rows, :]
        dp = jnp.zeros(experts.shape, dp_ref.dtype)
        for r in range(top_e.shape[1]):
            dp = jnp.where(top_e[:, r:r + 1] == experts, g[:, r:r + 1], dp)
        dp_ref[rows, :] = dp
        return carry

    jax.lax.fori_loop(0, dp_ref.shape[0] // 8, eight, 0)


@_traced_once
def _chosen_to_scores_kernel(top_e, g, num_experts, interpret):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    n, k = top_e.shape
    tile = min(TOKENS_TILE, -(-n // 8) * 8)
    padded = -(-n // tile) * tile
    top_e, g = (jnp.pad(x, ((0, padded - n), (0, 0))) for x in (top_e, g))
    vmem = functools.partial(pl.BlockSpec, memory_space=pltpu.VMEM)
    return pl.pallas_call(
        _chosen_to_scores_body,
        out_shape=jax.ShapeDtypeStruct((padded, num_experts), g.dtype),
        grid=(padded // tile,),
        in_specs=2 * [vmem((tile, k), lambda i: (i, 0))],
        out_specs=vmem((tile, num_experts), lambda i: (i, 0)),
        compiler_params=pltpu.CompilerParams(dimension_semantics=("parallel",)),
        interpret=interpret,
        name="router_top_k_bwd",
    )(top_e, g)[:n]


_top_scores.defvjp(_top_scores_fwd, _top_scores_bwd)


def plan_assignments(top_e: jax.Array, held: t.Tuple[int, int]) -> Plan:
    """The :class:`Plan` of the choices ``top_e`` ``(N, K)`` for the experts
    ``held = (lo, hi)``: ``order[:n_rows]`` is the held assignments by expert
    and inside an expert by flat index ``n * K + k`` (what a stable sort of
    every assignment by its held expert gives, to the element); past
    ``n_rows`` it holds indices in bounds that every reader masks. **A token's
    choices have to be distinct wherever they are held** (:func:`route`
    strikes out what it chose; choices held elsewhere may repeat), so a token
    holds at most ``min(K, hi - lo)`` rows here and ``order`` is ``N`` times
    that long; where fewer experts are held than a token chooses, a second
    choice of one held expert by one token gets no row.

    One sort of one ``int32`` word a candidate, the pairs (held expert,
    token) where fewer experts are held than a token chooses, otherwise the
    assignments themselves (module docstring)."""
    lo, hi = held
    n_held = hi - lo
    n, k = top_e.shape
    total = n * k
    if (n_held + 1) * total > jnp.iinfo(jnp.int32).max:
        raise ValueError(
            f"{n} tokens x {k} choices x {n_held} held experts: the plan's "
            "packed key (expert, flat index) does not fit an int32"
        )
    with jax.named_scope(scopes.TRUNK_MOE_PLAN):
        expert = top_e.astype(jnp.int32) - lo
        is_held = (expert >= 0) & (expert < n_held)
        experts = jnp.arange(n_held, dtype=jnp.int32)
        if n_held < k:
            chose = expert.T[None, :, :] == experts[:, None, None]  # (E_held, K, N)
            chosen = jnp.any(chose, axis=1)
            rounds = jnp.arange(k, dtype=jnp.int32)[None, :, None]
            flat = jnp.arange(n, dtype=jnp.int32) * k + jnp.max(
                jnp.where(chose, rounds, 0), axis=1
            )
            word = jnp.where(chosen, experts[:, None], n_held) * total + flat
            sizes = jnp.sum(chosen, axis=1, dtype=jnp.int32)
        else:
            key = jnp.where(is_held, expert, n_held).reshape(-1)
            word = key * total + jnp.arange(total, dtype=jnp.int32)
            sizes = jnp.sum(key[:, None] == experts[None, :], axis=0, dtype=jnp.int32)
        # Distinct among the live rows, and ties past ``n_rows`` are equal
        # values: asked for a stable sort, XLA:TPU sorts an iota beside them.
        order = jax.lax.rem(jax.lax.sort(word.reshape(-1), is_stable=False), total)
        return Plan(
            order=order, held=is_held, starts=jnp.cumsum(sizes) - sizes, sizes=sizes,
            n_rows=jnp.sum(sizes),
        )


def default_chunk_rows(n_tokens: int, top_k: int, n_held: int, n_experts: int) -> int:
    """Twice the expected number of held assignments, in whole pieces, at most
    all of them. A chunk sizes the buffers and what one grouped product is
    handed, no longer the row work (pieces, module docstring): so it keeps the
    margin that lets a router 1.2 times over the expected rows (the most the
    cell's counter read, PR 37) and a good deal worse run one chunk, since a
    second one costs the backward pass 1.1 ms of kernel-gradient sums."""
    expected = n_tokens * top_k * n_held / n_experts
    rows = -(-int(2 * expected) // PIECE_ROWS) * PIECE_ROWS
    return max(min(rows, n_tokens * top_k), 1)


# The transposed grouped product: contract the ragged rows, one (k, n) block
# a group.
_BY_GROUP = jax.lax.RaggedDotDimensionNumbers(
    dot_dimension_numbers=(((0,), (0,)), ((), ())),
    lhs_ragged_dimensions=[0], rhs_group_dimensions=[],
)

# Rows of one piece of a chunk: what one gather, one elementwise pass or one
# scatter-add moves. One 512-row tile of XLA:TPU's grouped product.
PIECE_ROWS = 512


def _mxu(x, bf16_dots: bool):
    """An operand as the TPU's default precision takes a float32 one: rounded
    to bfloat16 (``bf16_dots``, the attention kernels' switch of the same
    name; a configuration states it, ``SACConfig.trunk_bf16_dots``, and it
    means the same on every platform). On the TPU the grouped-product kernel
    rounds float32 operands the same way itself (same result to the bit and
    the same time alone; my chip run, PR 26), but handed bfloat16 it reads
    half the bytes, and inside the burst the grouped products went from 41.6
    to 28-31 ms a step."""
    return x.astype(_mxu_dtype(x.dtype, bf16_dots))


def _mxu_dtype(dtype, bf16_dots: bool):
    return jnp.bfloat16 if bf16_dots and dtype == jnp.float32 else dtype


def _gmm(x, w, sizes):
    """``x`` ``(rows, k)`` sorted by group times ``w`` ``(groups, k, n)``."""
    with jax.named_scope(scopes.TRUNK_MOE_PRODUCTS):
        return jax.lax.ragged_dot(x, w, sizes, preferred_element_type=jnp.float32)


def _gmm_by_group(x, g, sizes):
    """``(groups, k, n)``: each group's rows of ``x`` ``(rows, k)`` against
    its rows of ``g`` ``(rows, n)``."""
    with jax.named_scope(scopes.TRUNK_MOE_PRODUCTS):
        return jax.lax.ragged_dot_general(
            x, g, sizes, _BY_GROUP, preferred_element_type=jnp.float32
        )


def _silu_mul(a, b):
    return jax.nn.silu(a) * b


def _relu2(a):
    return jnp.square(jax.nn.relu(a))


# An expert's form: the function of its first products (one a kernel going
# in) that the down product takes. The number of kernels going in is the
# function's number of arguments.
FORMS = {"silu_gated": _silu_mul, "relu2": _relu2}


class _Chunk(t.NamedTuple):
    flat: jax.Array   # (R,) flat assignment index n*K+k of each sorted row
    tok: jax.Array    # (R,) its token
    live: jax.Array   # (R, 1) whether the row is a held assignment at all
    sizes: jax.Array  # (E_held,) rows of each expert inside the chunk
    n_live: jax.Array  # () held rows inside the chunk


def _chunk(plan: Plan, k: int, c, rows: int) -> _Chunk:
    r0 = c * rows
    flat = jax.lax.dynamic_slice(plan.order, (r0,), (rows,))
    live = r0 + jnp.arange(rows, dtype=jnp.int32) < plan.n_rows
    ends = jnp.minimum(plan.starts + plan.sizes, r0 + rows)
    sizes = jnp.maximum(ends - jnp.maximum(plan.starts, r0), 0)
    return _Chunk(
        flat, flat // k, live[:, None], sizes, jnp.clip(plan.n_rows - r0, 0, rows)
    )


# What a buffer that a loop fills piece by piece starts as: nothing (module
# docstring). A test puts NaN there.
_buffer = jax.lax.empty


def _weights(top_w, flat):
    """The routing weights ``(rows, 1)`` of the assignments ``flat``."""
    return jnp.take(top_w.reshape(-1), flat)[:, None]


def _pieces(ch: _Chunk, body, init):
    """``body(r0, rows_of, carry)`` for every piece of the chunk that holds a
    held row, in order: ``r0`` is the piece's first row and ``rows_of(x)`` its
    rows of a chunk-long ``x``. The trip count is data: the pieces past the
    held rows are never run."""
    rows = ch.tok.shape[0]
    piece = min(PIECE_ROWS, rows)

    def step(i, carry):
        r0 = i * piece

        def rows_of(x):
            return jax.lax.dynamic_slice_in_dim(x, r0, piece, axis=0)

        return body(r0, rows_of, carry)

    return jax.lax.fori_loop(0, -(-ch.n_live // piece), step, init)


def _filled(ch: _Chunk, width: int, dtype, piece_of):
    """A chunk-long ``(R, width)`` buffer whose pieces with a held row are
    ``piece_of(rows_of)``; the rows past them are never written and belong to
    no group."""
    def body(r0, rows_of, buf):
        return jax.lax.dynamic_update_slice_in_dim(buf, piece_of(rows_of), r0, axis=0)

    return _pieces(ch, body, _buffer((ch.tok.shape[0], width), dtype))


def _dispatched(ch: _Chunk, x):
    """The chunk's rows of ``x`` ``(N, H)``: one gather a piece."""
    return _filled(
        ch, x.shape[1], x.dtype, lambda rows_of: jnp.take(x, rows_of(ch.tok), axis=0)
    )


def _combined(ch: _Chunk, out, piece_of):
    """``out`` with the chunk's held rows ``piece_of(rows_of)`` added onto
    their tokens: one scatter-add a piece, the pieces in order, so a token's
    terms are added in the order of its sorted rows. Rows past the held ones
    belong to no group: whatever the grouped product left there is cut off."""
    def body(r0, rows_of, out):
        return out.at[rows_of(ch.tok)].add(jnp.where(rows_of(ch.live), piece_of(rows_of), 0))

    return _pieces(ch, body, out)


def _hidden(ch: _Chunk, xs, w_in, form: str, bf16_dots: bool):
    """The products of the dispatched rows with the kernels going in, and the
    hidden activations as the down product takes them."""
    pre = tuple(_gmm(xs, w, ch.sizes) for w in w_in)
    act = FORMS[form]
    hidden = _filled(
        ch, pre[0].shape[1], _mxu_dtype(pre[0].dtype, bf16_dots),
        lambda rows_of: _mxu(act(*(rows_of(a) for a in pre)), bf16_dots),
    )
    return pre, hidden


def _over_chunks(plan: Plan, rows: int, body, first):
    """``body(c, carry)`` for every chunk past the first that holds a held
    row; ``first`` is the carry the first chunk left."""
    n_chunks = -(-plan.order.shape[0] // rows)
    if n_chunks == 1:
        return first

    def step(c, carry):
        return jax.lax.cond(c * rows < plan.n_rows, lambda x: body(c, x), lambda x: x, carry)

    return jax.lax.fori_loop(1, n_chunks, step, first)


def _padded(plan: Plan, rows: int) -> Plan:
    """``order`` padded so that the last chunk's slice stays in bounds."""
    total = plan.order.shape[0]
    pad = -total % rows
    if not pad:
        return plan
    return plan._replace(order=jnp.pad(plan.order, (0, pad)))


def _if_any(run):
    """``run(u, w_in, w_down, top_w, plan, ...)`` where the call holds a row
    at all, else zeros (module docstring)."""
    def guarded(*operands):
        def nothing(*operands):
            return jax.tree_util.tree_map(
                lambda s: jnp.zeros(s.shape, s.dtype), jax.eval_shape(run, *operands)
            )

        plan = operands[4]
        return jax.lax.cond(plan.n_rows > 0, run, nothing, *operands)

    return guarded


def _forward(rows: int, bf16_dots: bool, form: str, u, w_in, w_down, top_w, plan: Plan):
    padded = _padded(plan, rows)
    # A token's row is rounded before it is gathered: the same values, half
    # the rows of a chunk and half the bytes.
    x = _mxu(u, bf16_dots)

    def body(c, out):
        ch = _chunk(padded, top_w.shape[1], c, rows)
        *going_in, down = (_mxu(w, bf16_dots) for w in (*w_in, w_down))
        _, hidden = _hidden(ch, _dispatched(ch, x), going_in, form, bf16_dots)
        y = _gmm(hidden, down, ch.sizes)
        return _combined(ch, out, lambda rows_of: rows_of(y) * _weights(top_w, rows_of(ch.flat)))

    return _over_chunks(plan, rows, body, body(0, jnp.zeros_like(u)))


def _backward(rows: int, bf16_dots: bool, form: str, u, w_in, w_down, top_w, plan: Plan, g):
    padded = _padded(plan, rows)
    x = _mxu(u, bf16_dots)

    def chunk(c, du, dw):
        """The chunk's kernel gradients, and ``du`` and ``dw`` with its rows'
        added. A chunk's activations are computed again from its gathered
        rows, as the forward pass computed them."""
        ch = _chunk(padded, top_w.shape[1], c, rows)
        *going_in, down = (_mxu(w, bf16_dots) for w in (*w_in, w_down))
        # The input gradients' products take a kernel transposed.
        *t_in, t_down = (jnp.swapaxes(w, 1, 2) for w in (*going_in, down))
        xs = _dispatched(ch, x)
        pre, hidden = _hidden(ch, xs, going_in, form, bf16_dots)
        y = _gmm(hidden, down, ch.sizes)

        def through_down(r0, rows_of, carry):
            gy, dw = carry
            g_rows = jnp.take(g, rows_of(ch.tok), axis=0)
            live, flat = rows_of(ch.live), rows_of(ch.flat)
            piece = _mxu(jnp.where(live, g_rows * _weights(top_w, flat), 0), bf16_dots)
            # d out / d weight of a row is <y_row, g_row>.
            d_rows = jnp.sum(jnp.where(live, rows_of(y) * g_rows, 0), axis=-1)
            return (
                jax.lax.dynamic_update_slice_in_dim(gy, piece, r0, axis=0),
                dw.at[flat].add(d_rows),
            )

        gy, dw = _pieces(
            ch, through_down, (_buffer((rows, g.shape[1]), _mxu_dtype(g.dtype, bf16_dots)), dw)
        )
        d_hidden = _gmm(gy, t_down, ch.sizes)

        def through_act(r0, rows_of, carry):
            _, vjp = jax.vjp(FORMS[form], *(rows_of(a) for a in pre))
            return tuple(
                jax.lax.dynamic_update_slice_in_dim(buf, _mxu(d, bf16_dots), r0, axis=0)
                for buf, d in zip(carry, vjp(rows_of(d_hidden)))
            )

        d_pre = _pieces(
            ch, through_act, tuple(_buffer(hidden.shape, hidden.dtype) for _ in pre)
        )
        dx = tuple(_gmm(d, t_w, ch.sizes) for d, t_w in zip(d_pre, t_in))
        kernels = (
            *(_gmm_by_group(xs, d, ch.sizes) for d in d_pre),
            _gmm_by_group(hidden, gy, ch.sizes),
        )
        du = _combined(
            ch, du, lambda rows_of: functools.reduce(operator.add, (rows_of(d) for d in dx))
        )
        return kernels, du, dw

    def body(c, carry):
        kernels, du, dw = carry
        more, du, dw = chunk(c, du, dw)
        # A kernel's gradient is summed chunk by chunk.
        return tuple(k + m for k, m in zip(kernels, more)), du, dw

    first = chunk(0, jnp.zeros_like(u), jnp.zeros(top_w.size, top_w.dtype))
    kernels, du, dw = _over_chunks(plan, rows, body, first)
    *d_in, d_down = (k.astype(w.dtype) for k, w in zip(kernels, (*w_in, w_down)))
    return du, tuple(d_in), d_down, dw.reshape(top_w.shape)


@functools.lru_cache(maxsize=None)
def _experts_for(rows: int, bf16_dots: bool, form: str = "silu_gated"):
    """The expert layer of ``form`` for chunks of ``rows``, with its
    hand-written backward pass. Under ``vmap`` (the data-parallel burst maps
    the update over its device axis, a population over its members) both
    passes run once a mapped element, in turn (``sequential_vmap``): the
    grouped product has no batched form on the TPU, and a batched
    ``lax.cond`` would run every chunk of every element."""
    forward = jax.custom_batching.sequential_vmap(
        _if_any(functools.partial(_forward, rows, bf16_dots, form))
    )
    backward = jax.custom_batching.sequential_vmap(
        _if_any(functools.partial(_backward, rows, bf16_dots, form))
    )

    @jax.custom_vjp
    def experts(u, w_in, w_down, top_w, plan):
        return forward(u, w_in, w_down, top_w, plan)

    def fwd(u, w_in, w_down, top_w, plan):
        return forward(u, w_in, w_down, top_w, plan), (u, w_in, w_down, top_w, plan)

    def bwd(res, g):
        return (*backward(*res, g), None)

    experts.defvjp(fwd, bwd)
    return experts


def expert_ffn(
    u: jax.Array, w_gate: jax.Array | None, w_up: jax.Array, w_down: jax.Array,
    top_e: jax.Array, top_w: jax.Array, held: t.Tuple[int, int],
    chunk_rows: int | None = None, num_experts: int | None = None,
    bf16_dots: bool = False, form: str = "silu_gated",
) -> t.Tuple[jax.Array, Plan]:
    """This chip's partial sum of the expert layer for tokens ``u`` ``(N, H)``.

    ``w_gate``/``w_up``: ``(hi - lo, H, F)``, ``w_down``: ``(hi - lo, F, H)``,
    the held experts' kernels; ``form`` (``FORMS``) says what an expert
    computes with them, and a form without a gate (``"relu2"``) takes
    ``w_gate=None``. ``top_e``/``top_w``: :func:`route`'s choices over all
    experts, **distinct inside a token wherever they are held** (choices
    held elsewhere may repeat): the plan counts one row for a token and a
    held expert, so a second choice of that expert by the token would be
    left out (:func:`plan_assignments`). Returns the ``(N, H)`` sum over each token's chosen experts that
    are held, and the :class:`Plan` (its counters). ``bf16_dots`` rounds the
    float32 operands of every grouped product to bfloat16 (float32
    accumulation and output), forward and backward."""
    n, k = top_e.shape
    n_held = held[1] - held[0]
    if chunk_rows is None:
        chunk_rows = default_chunk_rows(n, k, n_held, num_experts or n_held)
    plan = plan_assignments(top_e, held)
    chunk_rows = min(chunk_rows, plan.order.shape[0])
    if chunk_rows > PIECE_ROWS:  # whole pieces
        chunk_rows = -(-chunk_rows // PIECE_ROWS) * PIECE_ROWS
    top_w = jnp.where(plan.held, top_w, 0.0)  # an absent term has no gradient here
    w_in = (w_up,) if w_gate is None else (w_gate, w_up)
    experts = _experts_for(chunk_rows, bool(bf16_dots), form)
    return experts(u, w_in, w_down, top_w, plan), plan
