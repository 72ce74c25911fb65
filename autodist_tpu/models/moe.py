"""Mixture-of-experts MLP: top-k routing over all of a layer's experts,
computed for the experts this program HOLDS, with no token dropped.

The layer is told the router's width (``n_experts``), the experts a
token takes (``top_k``) and which experts it holds (``held = (first,
count)``; all by default). It routes over all of them, softmax then the
``top_k`` largest, their weights renormalised to sum to one, and
computes the part of the result its own experts give: ``sum over the
chosen e that are held of w_e * expert_e(x)``. What the experts held
elsewhere would add is left out. That is the layer each rank of an
expert-parallel job runs between its two exchanges; this module has no
exchange, and nothing that stands in for absent chips (one chip's share
of a deployment: the ``model-configs`` guide, §4). The parts of all the
shares add up to the whole layer (``tests/test_moe.py``).

How the held part is computed (the reference's dense-dispatch einsums
over a one-hot ``[b, s, e, capacity]`` tensor, which dropped what
overflowed a capacity, are gone):

* **route** (scope ``moe_route``): router, top-k, and the ORDER of the
  (token, choice) pairs whose expert is held: by expert, and inside an
  expert by token. Each expert's rows start on a tile of
  ``grouped_matmul.TILE_ROWS`` rows and are padded to whole tiles, so a
  tile is one expert's. The rows' token and weight come by ONE stable
  sort of the pairs by expert and a copy of each expert's run to its
  tiles (``_runs``); the same layout by token, ``row_of [t, held]`` for
  the combine, is ``start of the expert + the pair's rank there`` (a
  running count over the tokens). In the backward pass the rows'
  gradient goes back to the pairs the same way: the runs copied back
  and the sort undone by a sort. No single number is moved by an
  index: XLA does that at 4.5 ns apiece on a v5e (1.2 ms a scatter of
  262,144 pairs, 1.9 the gather that transposes it, 2.2 a
  ``take_along_axis``, all of which this replaced) where a sort of the
  pairs is 0.3 ms. The row buffer is sized for the worst case, every
  pair held: ``tokens x min(top_k, held)`` rows and a tile of padding an
  expert. NO ROW IS DROPPED at any routing. The order is made ONCE a
  step: what the backward pass reads of it goes by ``CHECKPOINT_NAMES``,
  which ``TransformerLM``'s ``remat=True`` keeps (``_block_fn``), so the
  block run again in the backward makes none of it; it still runs the
  router, the softmax and ``top_k``, whose own backward reads them (0.4
  ms a layer). Kept: the sorted ``pairs [t * k]``, ``token`` and
  ``weight`` by row, ``row_of [t, held]``, a tile's expert, the live
  tiles and the experts' sizes: integers and one f32 vector, ``4 x (t x
  k + 2 x rows + t x held)`` bytes, 5.3 MB a layer at 32,768 tokens, 8
  of 64, 16 held, beside the 453 MB a pass of the combine holds.
* **experts** (``moe_experts``) between **dispatch** (``moe_dispatch``):
  the rows are walked in CHUNKS of ``CHUNK_TILES`` tiles by a
  ``while_loop`` that stops after the last live tile, so the time
  follows the rows that are live and the temporaries one chunk, not the
  buffer: gather the chunk's token rows (``jnp.take``), one grouped
  product with the experts' ``[dim, hidden]`` (gate and up side by side
  for a gated expert), the activation, a second with ``[hidden, dim]``.
  The chunks' output rows are put side by side in the buffer of a PASS
  (``PASS_CHUNKS`` chunks, in x's dtype), and a pass's rows go back
  onto their tokens with their weights, added up in f32, by the kernel
  ``moe_combine`` (``kernels/moe_combine.py``), which uses the order
  above: the rows of one expert for a block of 128 tokens are one
  contiguous run, so a block's sum is built in VMEM from one fixed
  window an expert and written once; no scatter-add. Live rows beyond
  a pass take a further pass, onto the first one's sum. The backward
  pass is written out (``jax.custom_vjp``): it walks the chunks again,
  computes each chunk's forward again, adds the experts' weight
  gradients up in place, a group at a time (``grouped_matmul.gmm_dw``),
  and sends the rows' input gradients through the same passes of the
  same kernel, at weights of one; nothing a row long is kept between
  the forward and the backward.

The grouped products are the Pallas kernels of
``kernels/grouped_matmul.py`` (``moe_gmm``, ``moe_gmm_dx``,
``moe_gmm_dw``) and the combine that of ``kernels/moe_combine.py``,
always: under a mesh that shards the tokens or the experts the layer
runs on each device's shard in a manual region
(:meth:`MoeMlp._on_shards`). Each trace of the layer leaves its plan in
the loop ring as the point event ``moe.plan``
(``docs/design/observability.md``).

An auxiliary load-balancing loss (Switch eq. 4, over all the router's
experts) is returned beside the output, and the step's load as
``stats``: the rows held here and the largest load of a held expert.

The router's variants (``scoring``, ``select_bias``, ``scale``: DeepSeek-V3's
``sigmoid`` / ``noaux_tc`` / ``routed_scaling_factor``): sigmoid scores
over all the experts in f32, the ``top_k`` experts with the largest
``score + bias`` (the bias SELECTS only, takes no gradient and is not
updated here), weights ``scale x score / (sum of the chosen scores +
1e-20)``; the Switch loss does not apply to such scores and is zero.
``shared`` adds an always-on expert of that width for every token (scope
``moe_shared``), whole on every holder of a share: the shares' routed
parts and ONE shared expert add up to the layer.
"""
import functools

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name
from jax.sharding import PartitionSpec as P

from autodist_tpu import telemetry
from autodist_tpu.kernels import grouped_matmul as gm
from autodist_tpu.kernels import moe_combine as mc
from autodist_tpu.models.core import (Dense, GatedMlp, Mlp, Module,
                                      ParamDef)
from autodist_tpu.parallel.axes import (AXIS_DATA, active_manual_axes,
                                        current_mesh, live_mesh_axis,
                                        shard_map, unsharded_execution)

# Tiles a step of the chunk loop walks: 4096 rows, an expert's share of
# 32,768 tokens at 8 of 64. XLA's gather and the activation run on
# every row of a chunk, live or not, so a chunk is what a step wastes at
# most; the temporaries of a step are a chunk's.
CHUNK_TILES = 16
# What the backward pass reads of the order (`_order`), by the names a
# block's checkpoint keeps it under (models/transformer.py `_block_fn`),
# so that the recomputed block makes none of it again: integers and one
# f32 vector, 5.3 MB a layer at Mellum2's shape.
CHECKPOINT_NAMES = ('moe_pairs', 'moe_token', 'moe_weight', 'moe_tile_group',
                    'moe_live', 'moe_row_of', 'moe_sizes')
# Chunks whose rows one pass of the combine holds side by side: 98,304
# rows, 453 MB of bf16 at Mellum2's width, a third more than the cell's
# 16 experts hold of 32,768 tokens at an even load (its worst case is
# 266,240). A layer with more live rows takes a further pass, onto the
# first one's sum.
PASS_CHUNKS = 24


class MoeMlp(Module):
    """Top-k routed expert MLP. Input/output: [batch, seq, dim];
    ``apply`` returns ``(y, aux, stats)``."""

    def __init__(self, dim, hidden, n_experts, top_k=2, held=None,
                 dtype=jnp.float32, act=jax.nn.gelu, gated=False,
                 scoring='softmax', select_bias=False, scale=1.0, shared=0):
        if scoring not in ('softmax', 'sigmoid'):
            raise ValueError("scoring must be 'softmax' or 'sigmoid', not %r"
                             % (scoring,))
        self.dim, self.hidden = dim, hidden
        self.scoring, self.select_bias, self.scale = (scoring, select_bias,
                                                      float(scale))
        self.shared, self.shared_dim = None, int(shared)
        if shared:
            self.shared = GatedMlp(dim, shared, dtype=dtype, act=act) \
                if gated else Mlp(dim, shared, dtype=dtype, act=act,
                                  use_bias=False)
        self.n_experts = n_experts
        self.top_k = top_k
        self.first, self.held = held or (0, n_experts)
        if not 0 <= self.first <= self.first + self.held <= n_experts \
                or self.held < 1:
            raise ValueError('held=%r is no run of the %d experts'
                             % (held, n_experts))
        self.dtype = dtype
        self.act = act
        self.gated = gated
        self.router = Dense(dim, n_experts, 'embed', None,
                            use_bias=False, dtype=jnp.float32)

    def param_defs(self):
        # an expert's fan-in is its own dim or hidden, not the stack's;
        # a gated expert's gate (index 0: the activated half) and up
        # (index 1) lie side by side, as GatedMlp's: `up [held, dim, 2,
        # hidden]`; an expert without a gate (nemotron_h's relu2 ones)
        # has the one matrix, `up [held, dim, hidden]`
        up = (self.held, self.dim) + ((2,) if self.gated else ()) \
            + (self.hidden,)
        defs = {
            'router': self.router,
            'up': ParamDef(up, ('expert', 'embed')
                           + ((None,) if self.gated else ()) + ('mlp',),
                           'normal', self.dim ** -0.5),
            'down': ParamDef((self.held, self.hidden, self.dim),
                             ('expert', 'mlp', 'embed'), 'normal',
                             self.hidden ** -0.5),
        }
        if self.select_bias:
            defs['select_bias'] = ParamDef((self.n_experts,), (None,),
                                           'zeros')
        if self.shared is not None:
            defs['shared'] = self.shared
        return defs

    def apply(self, params, x):
        mesh = None if unsharded_execution() else current_mesh()
        if mesh is None:
            y, f, p, sizes = self._held_part(
                x, params['router'], params['up'], params['down'],
                self.first, params.get('select_bias'))
            total, largest = jnp.sum(sizes), jnp.max(sizes)
        else:
            y, f, p, total, largest = self._on_shards(mesh, params, x)
        if self.shared is not None:
            with jax.named_scope('moe_shared'):
                y = y + self.shared.apply(params['shared'], x)
        # load-balance aux loss (Switch eq. 4): e * sum_e f_e * P_e, f
        # the share of the tokens whose first choice is e and P the mean
        # of its probability; not a loss of sigmoid scores
        n = x.shape[0] * x.shape[1]
        aux = self.n_experts * jnp.sum(f * p) / (n * n) \
            if self.scoring == 'softmax' else jnp.zeros((), jnp.float32)
        stats = jnp.stack([total, largest]).astype(jnp.float32)
        return y, aux, stats

    def _held_part(self, x, router, up, down, first, bias=None):
        """What the experts ``first ..`` of ``up`` / ``down`` (all their
        hidden units or a run of them) add for the tokens of ``x [b, s,
        d]``, on device-local data: ``(y, first choices counted by
        expert [e], probabilities summed by expert [e], rows of each of
        these experts)``."""
        b, s, d = x.shape
        held, hidden = down.shape[0], down.shape[1]
        tokens = x.reshape(b * s, d)
        with jax.named_scope('moe_route'):
            probs, weights, idx = self._route(router, tokens, bias)
            order = _order(idx - first, weights, held)
        _note_plan(order, d, self.dtype, scoring=self.scoring,
                   bias=bias is not None, scale=self.scale,
                   shared=self.shared_dim)
        y = _experts(functools.partial(_hidden, self.act, self.gated, hidden),
                     tokens.astype(self.dtype), up.reshape(held, d, -1), down,
                     order['token'], order['weight'], order['tile_group'],
                     order['live'], order['row_of'], order['token_weight'],
                     order['pairs'], order['sizes'], weights)
        f = jnp.sum(jax.nn.one_hot(idx[:, 0], self.n_experts,
                                   dtype=jnp.float32), axis=0)
        return y.reshape(b, s, d), f, jnp.sum(probs, axis=0), order['sizes']

    def _on_shards(self, mesh, params, x):
        """:meth:`_held_part` on each device's shard, in a manual region
        (GSPMD cannot partition the kernels' opaque calls; as
        ``MultiHeadAttention._kernel_attention``): the tokens by their
        batch over the data axis, the experts over the axis of
        ``'expert'`` and their hidden units over that of ``'mlp'``. A
        device computes what ITS experts and hidden units add for ITS
        tokens; the parts are added up over the axes that divide the
        experts (the tokens are whole on each of them: this is an
        all-reduce of partial sums, not an exchange of tokens), the
        counts over all. The region is manual over every axis that is
        not manual already (inside a pipeline stage, over the rest)."""
        manual = active_manual_axes()
        live = [a for a, n in mesh.shape.items() if n > 1 and a not in manual]
        data = AXIS_DATA if AXIS_DATA in live else None
        by_expert, by_hidden = (
            a if a in live else None
            for a in (live_mesh_axis('expert'), live_mesh_axis('mlp')))
        if data and x.shape[0] % mesh.shape[data] \
                or by_expert and self.held % mesh.shape[by_expert] \
                or by_hidden and self.hidden % mesh.shape[by_hidden]:
            raise ValueError(
                'MoeMlp: batch %d, %d held experts and %d hidden units do '
                'not divide over the mesh %s' % (x.shape[0], self.held,
                                                 self.hidden, dict(mesh.shape)))
        over_experts = tuple(a for a in (by_expert, by_hidden) if a)

        def part(x, router, up, down, *bias):
            first = self.first
            if by_expert:
                first = first + jax.lax.axis_index(by_expert) * down.shape[0]
            y, f, p, sizes = self._held_part(x, router, up, down, first,
                                             *bias)
            if over_experts:
                y = jax.lax.psum(y, over_experts)
            if data:
                f, p, sizes = jax.lax.psum((f, p, sizes), data)
            total, largest = jnp.sum(sizes), jnp.max(sizes)
            if by_expert:
                total = jax.lax.psum(total, by_expert)
                largest = jax.lax.pmax(largest, by_expert)
            return y, f, p, total, largest

        tokens = P(data, None, None)
        gate = (None,) if self.gated else ()
        bias = (params['select_bias'],) if self.select_bias else ()
        return shard_map(
            part, None if manual else mesh,
            (tokens, P(), P(by_expert, None, *gate, by_hidden),
             P(by_expert, by_hidden, None)) + (P(),) * len(bias),
            (tokens, P(), P(), P(), P()),
            axis_names=set(live) if manual else None)(
                x, params['router'], params['up'], params['down'], *bias)

    def _route(self, router, tokens, bias=None):
        """``(probs [t, e], weights [t, k], idx [t, k])``: the softmax
        over all the experts in f32, and of its ``top_k`` largest the
        weights, renormalised, and the experts. With sigmoid scoring the
        scores, the ``top_k`` experts by ``score + bias`` and their
        weights from the scores alone, ``scale x score / sum``."""
        logits = self.router.apply(router, tokens.astype(jnp.float32))
        if self.scoring == 'softmax':
            probs = jax.nn.softmax(logits, axis=-1)
            vals, idx = jax.lax.top_k(probs, self.top_k)
            return probs, vals / jnp.maximum(
                jnp.sum(vals, -1, keepdims=True), 1e-9), idx
        probs = jax.nn.sigmoid(logits)
        _, idx = jax.lax.top_k(
            probs if bias is None else probs + jax.lax.stop_gradient(
                bias.astype(jnp.float32)), self.top_k)
        # the chosen experts' own scores, by a mask and not by an index
        # (XLA moves single numbers by an index at 4.5 ns apiece)
        vals = jnp.sum(jnp.where(
            idx[:, :, None] == jnp.arange(self.n_experts)[None, None, :],
            probs[:, None, :], 0.0), axis=-1)
        return probs, self.scale * vals / (
            jnp.sum(vals, -1, keepdims=True) + 1e-20), idx


def _hidden(act, gated, f, u):
    """``h`` of a chunk's first product ``u``, in f32: for a gated
    expert ``u [rows, 2 f]`` holds gate and up side by side and ``h =
    act(gate) * up``; without a gate ``u [rows, f]`` and ``h = act(u)``."""
    return act(u[:, :f]) * u[:, f:] if gated else act(u)


def buffer_rows(tokens, top_k, held):
    """Rows of the buffer the held pairs are laid out in, at the worst
    case (every pair of every token held, as far as ``top_k`` and
    ``held`` allow; a tile of padding an expert), in whole chunks."""
    chunk = CHUNK_TILES * gm.TILE_ROWS
    rows = tokens * min(top_k, held) + held * gm.TILE_ROWS
    return -(-rows // chunk) * chunk


def _order(local, weights, held):
    """The layout of the pairs ``(token, choice)`` whose expert
    ``local[t, j]`` (counted from the first held) is held, ``0 <= local <
    held``: rows by expert, each expert from a tile's start, inside an
    expert by token. Returns ``token`` and ``weight`` of every row of
    the buffer (``[buffer_rows]``; a padding row is token 0 at weight
    0), each tile's expert ``tile_group``, the number of ``live`` tiles
    (``[1]``) and the experts' ``sizes`` in rows; and the same layout by
    token, for the combine: ``row_of [t, held]``, the row of the pair
    ``(t, expert)`` or -1, and ``token_weight [t, held]``, its weight;
    and ``pairs [t * k]``, the pairs ``t * k + j`` as the sort left them
    (the held ones first, in the rows' order), by which the backward
    pass takes the rows' gradient to ``weights`` (:func:`_experts`): no
    gradient goes through anything returned here."""
    t, k = local.shape
    tile = gm.TILE_ROWS
    rows = buffer_rows(t, k, held)
    is_held = jnp.logical_and(local >= 0, local < held)
    pair = jnp.logical_and(
        local[:, :, None] == jnp.arange(held)[None, None, :],
        is_held[:, :, None])                                   # [t, k, held]
    chosen = jnp.any(pair, axis=1).astype(jnp.int32)           # [t, held]
    token_weight = jnp.sum(jnp.where(
        pair, weights.astype(jnp.float32)[:, :, None], 0.0), axis=1)
    sizes = jnp.sum(chosen, axis=0)
    rank = jnp.cumsum(chosen, axis=0) - chosen                 # [t, held]
    tile_end = jnp.cumsum(-(-sizes // tile))
    start, first = _run_starts(sizes)                          # [held]
    row_of = jnp.where(chosen > 0, start[None, :] + rank, -1)
    tile_group = jnp.minimum(jnp.searchsorted(
        tile_end, jnp.arange(rows // tile), side='right'),
        held - 1).astype(jnp.int32)
    # token and weight of every row by ONE stable sort of the pairs by
    # expert (they come by token) and a copy of each expert's run to its
    # tiles: no scatter by row
    _, pairs, weight = jax.lax.sort(
        (jnp.where(is_held, local, held).ravel(),
         jnp.arange(t * k, dtype=jnp.int32),
         jax.lax.stop_gradient(weights).astype(jnp.float32).ravel()),
        num_keys=1, is_stable=True)
    in_run = (jnp.arange(rows).reshape(-1, tile)
              < (start + sizes)[tile_group][:, None]).ravel()

    def by_row(sorted_pairs):
        return jnp.where(in_run, _runs(sorted_pairs, first, start, t, rows),
                         0)
    order = {'pairs': pairs, 'token': by_row(pairs // k),
             'weight': by_row(weight), 'tile_group': tile_group,
             'live': tile_end[-1:].astype(jnp.int32),
             'row_of': row_of.astype(jnp.int32), 'sizes': sizes,
             'token_weight': jax.lax.stop_gradient(token_weight)}
    return {key: checkpoint_name(x, 'moe_' + key)
            if 'moe_' + key in CHECKPOINT_NAMES else x
            for key, x in order.items()}


def _run_starts(sizes):
    """Where each expert's run starts among the rows (on a tile) and
    among the sorted pairs, for the experts' ``sizes``."""
    tiles = -(-sizes // gm.TILE_ROWS)
    return ((jnp.cumsum(tiles) - tiles) * gm.TILE_ROWS,
            jnp.cumsum(sizes) - sizes)


def _runs(x, source, target, length, size):
    """``size`` zeros with ``x [source[e]:source[e] + length]`` copied to
    ``target[e]`` for each expert ``e`` in turn: a run in another place;
    what it carries past its own end the next one overwrites, and the
    caller masks the rest."""
    x = jnp.pad(x, (0, length))

    def run(e, out):
        return jax.lax.dynamic_update_slice_in_dim(
            out, jax.lax.dynamic_slice_in_dim(x, source[e], length),
            target[e], 0)
    return jax.lax.fori_loop(0, source.shape[0], run, jnp.zeros(
        (size + length,), x.dtype))[:size]


# ---------------------------------------------------------------------------
# the held experts over the ordered rows
# ---------------------------------------------------------------------------

def _chunk(i, token, weight, tile_group, live):
    """Chunk ``i`` of the rows: its tokens, weights, tiles' experts, live
    tiles (``[1]``), and which of its rows lie in a live tile."""
    rows = CHUNK_TILES * gm.TILE_ROWS
    at = jax.lax.dynamic_slice_in_dim
    here = jnp.clip(live - i * CHUNK_TILES, 0, CHUNK_TILES)
    valid = (jnp.arange(rows) < here[0] * gm.TILE_ROWS)[:, None]
    return (at(token, i * rows, rows), at(weight, i * rows, rows),
            at(tile_group, i * CHUNK_TILES, CHUNK_TILES), here, valid)


def _chunks(live):
    return -(-live[0] // CHUNK_TILES)


def pass_chunks(rows):
    """Chunks a pass of the combine takes, of a buffer of ``rows``."""
    return min(PASS_CHUNKS, rows // (CHUNK_TILES * gm.TILE_ROWS))


def _walk(live, row_of, token_weight, rows, like, step, state):
    """The live chunks walked a PASS at a time: ``step(chunk, state)``
    gives ``(the chunk's rows for the combine [chunk rows, dim], which of
    them lie in a live tile, state)``; a pass puts its chunks' rows side
    by side in one buffer (``pass_chunks`` chunks of ``like``'s dtype)
    and adds them up onto their tokens (``kernels/moe_combine.py``), the
    first pass over nothing, each further one onto the sum so far.
    Returns ``(sum [tokens, dim] f32, state)``."""
    chunk_rows = CHUNK_TILES * gm.TILE_ROWS
    per_pass = pass_chunks(rows)
    chunks = _chunks(live)

    def one_pass(carry):
        j, total, state = carry
        first = j * per_pass
        here = jnp.clip(chunks - first, 0, per_pass)

        def chunk(carry):
            i, buffer, state = carry
            out, valid, state = step(first + i, state)
            with jax.named_scope('moe_dispatch'):
                # rows of no live tile as zeros: the kernels leave them
                # unwritten, and a window of the combine may hold them
                buffer = jax.lax.dynamic_update_slice_in_dim(
                    buffer, jnp.where(valid, out, 0).astype(buffer.dtype),
                    i * chunk_rows, axis=0)
            return i + 1, buffer, state

        with jax.named_scope('moe_dispatch'):
            buffer = mc.unwritten(per_pass * chunk_rows, like.shape[1],
                                  like.dtype)
        _, buffer, state = jax.lax.while_loop(
            lambda carry: carry[0] < here, chunk,
            (jnp.int32(0), buffer, state))
        with jax.named_scope('moe_dispatch'):
            at = row_of - first * chunk_rows
            at = jnp.where(jnp.logical_and(at >= 0, at < here * chunk_rows),
                           at, -1)
            total = mc.combine(buffer, at, token_weight,
                               limit=here * chunk_rows, onto=total,
                               fresh=j == 0)
        return j + 1, total, state

    with jax.named_scope('moe_dispatch'):
        total = mc.unwritten(like.shape[0], like.shape[1], jnp.float32)
    # one pass at least: with no live row it writes the zeros
    _, total, state = jax.lax.while_loop(
        lambda carry: jnp.logical_or(carry[0] == 0,
                                     carry[0] * per_pass < chunks),
        one_pass, (jnp.int32(0), total, state))
    return total, state


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _experts(hidden, x, w_up, w_down, token, weight, tile_group, live,
             row_of, token_weight, pairs, sizes, weights):
    """``out[t] = sum over the rows r of token t of weight[r] * (hidden(
    x[t] @ w_up[e_r]) @ w_down[e_r])`` for ``x [tokens, dim]``, the held
    experts' ``w_up [held, dim, n * hidden]`` and ``w_down [held, hidden,
    dim]`` (f32; multiplied in x's dtype) and the rows' layout
    (:func:`_order`); ``[tokens, dim]`` in x's dtype. The rows' weights
    take their gradient as the pairs' ``weights [t, k]``, through
    ``pairs`` and ``sizes``."""
    return _experts_fwd(hidden, x, w_up, w_down, token, weight, tile_group,
                        live, row_of, token_weight, pairs, sizes, weights)[0]


def _experts_fwd(hidden, x, w_up, w_down, token, weight, tile_group, live,
                 row_of, token_weight, pairs, sizes, weights):
    up, down = w_up.astype(x.dtype), w_down.astype(x.dtype)

    def step(i, state):
        tok, _, groups, here, valid = _chunk(i, token, weight, tile_group,
                                             live)
        with jax.named_scope('moe_dispatch'):
            xs = jnp.take(x, tok, axis=0)
        with jax.named_scope('moe_experts'):
            u = gm.gmm(xs, up, groups, here)
            h = jnp.where(valid, hidden(u.astype(jnp.float32)), 0.0)
            y = gm.gmm(h.astype(x.dtype), down, groups, here)
        return y, valid, state

    out, _ = _walk(live, row_of, token_weight, token.shape[0], x, step, ())
    return out.astype(x.dtype), (x, w_up, w_down, token, weight, tile_group,
                                 live, row_of, pairs, sizes)


def _experts_bwd(hidden, res, dout):
    (x, w_up, w_down, token, weight, tile_group, live, row_of, pairs,
     sizes) = res
    up, down = w_up.astype(x.dtype), w_down.astype(x.dtype)

    def step(i, state):
        d_up, d_down, d_weight = state
        tok, wt, groups, here, valid = _chunk(i, token, weight, tile_group,
                                              live)
        with jax.named_scope('moe_dispatch'):
            xs = jnp.take(x, tok, axis=0)
            dy = jnp.where(valid, jnp.take(dout, tok, axis=0), 0)
        with jax.named_scope('moe_experts'):
            u = gm.gmm(xs, up, groups, here)
            h, h_vjp = jax.vjp(hidden, u.astype(jnp.float32))
            h = jnp.where(valid, h, 0.0)
            # d(weight) and d(h) from the unweighted dy @ down^T
            dh = jnp.where(valid, gm.gmm(dy, down, groups, here,
                                         transposed=True).astype(jnp.float32),
                           0.0)
            d_wt = jnp.sum(h * dh, axis=-1)
            du = h_vjp(dh * wt[:, None])[0].astype(x.dtype)
            d_down = gm.gmm_dw(h.astype(x.dtype),
                            (dy.astype(jnp.float32)
                             * wt[:, None]).astype(x.dtype),
                            groups, here, d_down)
            d_up = gm.gmm_dw(xs, du, groups, here, d_up)
            dxs = gm.gmm(du, up, groups, here, transposed=True)
        with jax.named_scope('moe_dispatch'):
            d_weight = jax.lax.dynamic_update_slice_in_dim(
                d_weight, d_wt, i * d_wt.shape[0], axis=0)
        return dxs, valid, (d_up, d_down, d_weight)

    dx, (d_up, d_down, d_weight) = _walk(
        live, row_of, None, token.shape[0], x, step,
        (jnp.zeros(w_up.shape, jnp.float32),
         jnp.zeros(w_down.shape, jnp.float32),
         jnp.zeros(weight.shape, jnp.float32)))
    with jax.named_scope('moe_route'):
        # the rows' gradient back to the pairs: each expert's run to
        # where the sort had it, then the sort undone by one of its own
        n = pairs.shape[0]
        by_pairs = _runs(d_weight, *_run_starts(sizes), x.shape[0], n)
        _, d_weights = jax.lax.sort(
            (pairs, jnp.where(jnp.arange(n) < jnp.sum(sizes), by_pairs, 0.0)),
            num_keys=1)
        d_weights = d_weights.reshape(x.shape[0], -1)
    return (dx.astype(x.dtype), d_up.astype(w_up.dtype),
            d_down.astype(w_down.dtype), None, None, None, None, None, None,
            None, None, d_weights)


_experts.defvjp(_experts_fwd, _experts_bwd)


def _note_plan(order, dim, dtype, **router):
    """One ``moe.plan`` point event a trace of the layer: how its rows
    move between the tokens' order and the experts' (``rows`` and
    ``buffer_bytes``: of the buffer a pass of the combine holds) and how
    that order is made and kept (``order_scatters``: single numbers a
    trace of the layer moves by an index, by row; ``order_saved_bytes``:
    of what ``CHECKPOINT_NAMES`` name); ``router``: the router's variant
    (``scoring``, ``bias``, ``scale``, ``shared``)."""
    chunks = pass_chunks(order['token'].shape[0])
    held = chunks * CHUNK_TILES * gm.TILE_ROWS
    kept = [order[name[len('moe_'):]] for name in CHECKPOINT_NAMES]
    telemetry.get().loop_event(
        'moe.plan', rows=held, chunk_tiles=CHUNK_TILES, pass_chunks=chunks,
        token_block=mc.TOKEN_BLOCK, window_rows=mc.WINDOW_ROWS,
        combine='pallas', gather='xla',
        buffer_bytes=held * dim * jnp.dtype(dtype).itemsize,
        order='sort', order_scatters=0,
        order_saved_bytes=sum(x.size * x.dtype.itemsize for x in kept),
        **router)
