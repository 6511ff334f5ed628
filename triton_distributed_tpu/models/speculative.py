"""Self-drafting speculative decoding: n-gram/tree draft + one-forward
verify.

Steady-state decode pays one full target forward per emitted token —
the serial bottleneck the paper's philosophy (hide latency behind work
already in flight, arXiv:2504.19442) says to amortize. Speculative
decoding does exactly that on the sequential-decode axis: draft K cheap
candidate tokens, score ALL of them in ONE target forward through the
existing chunked paged-prefill path (``Qwen3.prefill_paged_chunk``
returns per-position logits with ``all_logits=True``), accept the
longest correct prefix, and roll the KV back past the rejection point
(``paged_kv_cache.rollback_kv``). One target step then emits
``accepted + 1`` tokens instead of one.

**Self-drafting**: the drafter is a prompt-lookup n-gram table over the
request's OWN prompt + generated tokens (the PLD / lookahead-free
idea) — no second model, no extra weights, runs anywhere the engine
runs. Repetitive and structured traffic (code, templated answers,
retrieval-heavy prompts, greedy cycles) drafts extremely well; chaotic
text degrades gracefully to K=0, i.e. plain decode.

**Exactness**: greedy acceptance compares each draft against the
argmax of the target logits at its position — output is bit-identical
to non-speculative greedy decode. For ``temperature>0`` the acceptance
is the standard rejection-sampling rule specialized to a deterministic
(delta) proposal: accept draft ``d`` with probability ``p(d)`` under
the FILTERED target distribution (``sampling.target_probs`` — the very
distribution ``sampling.sample`` draws from), and on rejection sample
from the residual ``p`` with ``d`` zeroed, renormalized. The emitted
distribution is exactly the target's (tests carry the statistical
proof).

**Acceptance bookkeeping** (the T3-style tracking/trigger discipline,
arXiv:2401.16677): per-slot ``SpecState`` counts proposed/accepted and
adapts K — additive growth on full acceptance, multiplicative back-off
on any rejection — so a slot whose traffic stops drafting well stops
paying verify overhead.

**Tree speculation**: a linear draft bets everything on ONE
continuation; when the radix tree (or the KV tier's spilled chains)
has seen SEVERAL continuations of the slot's suffix, ``TreeDraft``
stacks them into a token trie and verifies every branch in the SAME
single forward. The chunk already pads to ``round_chunk`` rows, so the
extra branches ride in rows a linear draft would have wasted on
padding. A tree-attention mask (additive 0/-1e30 bias threaded down to
the flash kernel) keeps siblings invisible to each other, and each
node ropes at ``kv + depth`` — so an accepted branch's KV rows are
bit-identical to the rows linear decode would have written, and the
commit is a plain row-move (``paged_kv_cache.move_kv_rows``) followed
by the usual kv_len rollback. Acceptance walks the tree root-down
drawing the TARGET token first (argmax, or one per-request subkey per
emitted token — the same key consumption as non-speculative decode)
and descending into the drafted child that matches: the emitted stream
never depends on the tree's shape, so greedy stays bit-identical,
sampling stays exactly distribution-preserving, and seeded replays
stay bit-exact even when the draft source (another request's radix
residue) is not replayable.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from triton_distributed_tpu.models import sampling
from triton_distributed_tpu.models.paged_kv_cache import gather_bucket
from triton_distributed_tpu.models.prefix_cache import round_chunk
from triton_distributed_tpu.obs import events as obs_events
from triton_distributed_tpu.runtime.faults import fault_point, mutate_point


class NGramDraft:
    """Prompt-lookup drafter: an n-gram table over one request's token
    history (prompt + every emitted token).

    For each n in ``[min_ngram, max_ngram]`` the table maps every
    n-gram to its two most recent end positions. Drafting takes the
    history's tail n-gram (longest n first), finds its PREVIOUS
    occurrence, and proposes the tokens that followed it — the
    continuation the sequence used last time it was here.
    """

    def __init__(self, max_ngram: int = 3, min_ngram: int = 1):
        if not 1 <= min_ngram <= max_ngram:
            raise ValueError(
                f"need 1 <= min_ngram <= max_ngram, got "
                f"[{min_ngram}, {max_ngram}]"
            )
        self.history: list[int] = []
        # n → {ngram tuple: (latest end pos, previous end pos | None)}
        self._index: dict[int, dict] = {
            n: {} for n in range(min_ngram, max_ngram + 1)
        }

    def observe(self, tokens) -> None:
        """Append ``tokens`` to the history, updating every n-gram's
        latest/previous occurrence incrementally (O(ngrams) per
        token)."""
        for t in tokens:
            self.history.append(int(t))
            end = len(self.history)
            for n, idx in self._index.items():
                if end >= n:
                    key = tuple(self.history[end - n:end])
                    prev = idx.get(key)
                    idx[key] = (end, prev[0] if prev is not None else None)

    def propose(self, k: int) -> list[int]:
        """Up to ``k`` draft tokens continuing the history's tail, from
        the longest n-gram with a previous occurrence; ``[]`` when
        nothing matches (the caller decodes normally)."""
        end = len(self.history)
        if k <= 0 or end == 0:
            return []
        for n in sorted(self._index, reverse=True):
            if end < n:
                continue
            entry = self._index[n].get(tuple(self.history[end - n:end]))
            if entry is None:
                continue
            # The latest occurrence IS the tail itself; the previous
            # one (if any) carries the continuation.
            pos = entry[1] if entry[0] == end else entry[0]
            if pos is None:
                continue
            cont = self.history[pos:pos + k]
            if cont:
                return list(cont)
        return []


class SpecState:
    """Per-slot speculative state: the drafter plus adaptive draft
    length K and accept/propose counters.

    The K controller tracks ACCEPTED-RUN length rather than classic
    AIMD: a fully accepted draft grows K by 2 (the run was at least as
    long as we dared), a rejection resets K to ``accepted + 1`` (next
    time, dare one past the run we actually got), floored at ``k_min``.
    A rejected verify still emits one token, so over-drafting costs
    only the wasted tail compute of one chunk — the controller's job is
    to bound that waste when acceptance collapses, not to give up
    drafting on the first miss.

    Tree mode adds a WIDTH ledger (``record_tree``): a full-depth
    accept widens the next tree by one branch (cap ``w_max``), a
    zero-accept round narrows it by one — at width 1 the slot is back
    to today's linear chain (or no draft at all when the drafter goes
    quiet), so cold traffic pays nothing for the tree machinery."""

    def __init__(
        self,
        k_max: int,
        *,
        k_min: int = 1,
        max_ngram: int = 3,
        min_ngram: int = 1,
        w_max: int = 1,
    ):
        self.k_max = max(int(k_max), 1)
        self.k_min = max(min(int(k_min), self.k_max), 1)
        self.k = self.k_max
        self.w_max = max(int(w_max), 1)
        self.width = self.w_max
        self.draft = NGramDraft(max_ngram, min_ngram)
        self.proposed = 0
        self.accepted = 0

    def observe(self, tokens) -> None:
        self.draft.observe(tokens)

    def propose(self, budget: int) -> list[int]:
        """Draft up to ``min(current K, budget)`` tokens."""
        return self.draft.propose(min(self.k, int(budget)))

    def record(self, proposed: int, accepted: int) -> None:
        """Fold one verify's outcome into the counters + adaptive K."""
        self.proposed += proposed
        self.accepted += accepted
        if proposed:
            if accepted == proposed:
                self.k = min(self.k + 2, self.k_max)
            else:
                self.k = min(max(accepted + 1, self.k_min), self.k_max)

    def record_tree(self, nodes: int, depth: int, accepted: int) -> None:
        """Fold one TREE verify: ``nodes`` drafted trie nodes (root
        excluded), ``depth`` the deepest drafted path, ``accepted`` the
        accepted path length. K adapts on accepted-vs-depth — the
        per-path analog of the linear rule — and width widens on a
        full-depth accept, narrowing back toward linear when a whole
        tree missed."""
        self.proposed += nodes
        self.accepted += accepted
        if nodes:
            if depth and accepted >= depth:
                self.k = min(self.k + 2, self.k_max)
                self.width = min(self.width + 1, self.w_max)
            else:
                self.k = min(max(accepted + 1, self.k_min), self.k_max)
                if accepted == 0:
                    self.width = max(self.width - 1, 1)

    @property
    def accept_rate(self) -> float:
        return self.accepted / max(self.proposed, 1)


def cap_draft(k: int, kv_len: int, budget: int, max_length: int) -> int:
    """Largest usable draft length this step: at most ``k``, at most
    ``budget - 1`` (the verify emits up to ``draft + 1`` tokens — never
    draft past the generation budget, so verify overshoot can't blow
    the page capacity the admission guard sized), and small enough that
    the PADDED chunk (``round_chunk(draft + 1)``) stays under
    ``max_length`` — pad rows write KV too, and past ``max_length`` the
    table runs out of entries. Returns ``-1`` when not even a
    zero-draft chunk fits (the caller must fall back to a plain decode
    step for this slot)."""
    k = min(int(k), int(budget) - 1)
    while k >= 0 and int(kv_len) + round_chunk(k + 1) > int(max_length):
        k -= 1
    return k


def verify_greedy(logits: np.ndarray, draft: list[int]) -> tuple[int, int]:
    """Greedy acceptance: ``logits [n+1, V]`` are the target's
    per-position outputs for inputs ``[pending, d_1..d_n]``. Accept
    ``d_i`` while it equals the argmax at its position; the token at
    the first mismatch (or the bonus position after a full accept) is
    emitted from the target's own argmax — so the emitted stream is
    exactly what non-speculative greedy decode would produce. Returns
    ``(accepted, next_token)``."""
    preds = np.argmax(logits, axis=-1)
    a = 0
    while a < len(draft) and int(preds[a]) == int(draft[a]):
        a += 1
    return a, int(preds[a])


def verify_sampled(
    logits: np.ndarray,
    draft: list[int],
    key: jax.Array,
    temperature: float,
    top_p: float = 1.0,
    top_k: int = 0,
) -> tuple[int, int, jax.Array]:
    """Distribution-preserving acceptance for ``temperature > 0``.

    With a deterministic (delta) draft proposal, the rejection-sampling
    rule collapses to: accept ``d_i`` with probability ``p_i(d_i)``
    under the filtered target distribution; on rejection, emit a sample
    of the residual ``p_i`` with ``d_i`` zeroed and renormalized. The
    marginal of each emitted token is exactly ``p_i`` — speculative
    sampling changes latency, never the distribution. Returns
    ``(accepted, next_token, key)``.
    """
    n = len(draft)
    probs = np.asarray(
        sampling.target_probs(
            jnp.asarray(logits[: n + 1]), temperature, top_p, top_k
        )
    )
    for i, d in enumerate(draft):
        d = int(d)
        key, sub = jax.random.split(key)
        if float(jax.random.uniform(sub)) < float(probs[i, d]):
            continue
        resid = probs[i].astype(np.float64)
        resid[d] = 0.0
        total = resid.sum()
        key, sub = jax.random.split(key)
        if total <= 0.0:
            # p(d) was numerically 1 yet the draw rejected — the
            # residual is empty; the target distribution IS d's
            # one-hot, so resample it directly.
            nxt = int(
                sampling.sample(
                    jnp.asarray(logits[i]), sub, temperature, top_p, top_k
                )
            )
        else:
            nxt = int(
                jax.random.categorical(sub, jnp.log(jnp.asarray(resid / total)))
            )
        return i, nxt, key
    key, sub = jax.random.split(key)
    bonus = int(
        sampling.sample(jnp.asarray(logits[n]), sub, temperature, top_p, top_k)
    )
    return n, bonus, key


def spec_verify_slot(
    model,
    cache,
    slot: int,
    pending: int,
    draft: list[int],
    kv_len: int,
    mode,
    *,
    key: jax.Array | None = None,
    temperature: float = 0.0,
    top_p: float = 1.0,
    top_k: int = 0,
):
    """One speculative verify of ``slot``: run ``[pending] + draft``
    through a single chunked paged-prefill forward (per-position
    logits), accept a prefix, and return
    ``(emitted tokens, cache, accepted, key)``. ``emitted`` is None
    when the chunk produced non-finite logits — the returned cache is
    still valid (the old one was donated to the chunk program) and the
    caller must fail this slot's request as ``nan_logits``.

    The chunk program writes KV for every input row and sets the slot's
    device ``kv_len`` to ``kv_len + 1 + len(draft)``; the CALLER owns
    the rollback to ``kv_len + accepted + 1`` (host-authoritative
    engines resync their tables, the fixed-batch engine calls
    ``rollback_kv``). Emitted tokens are ``draft[:accepted]`` plus one
    token from the target's own logits — the correction at the first
    mismatch, or the bonus token after a full accept — so every verify
    emits at least one token.
    """
    fault_point("spec.verify", slot=slot)
    toks = [int(pending)] + [int(d) for d in draft]
    n = len(toks)
    c = round_chunk(n)
    page = int(cache.k_pages.shape[3])
    pps = int(cache.page_table.shape[1])
    buf = np.zeros(c, np.int32)
    buf[:n] = toks
    kv_pages = gather_bucket(int(kv_len) + c, page, pps)
    logits, cache = model.prefill_paged_chunk(
        buf, slot, int(kv_len), int(kv_len) + n, n - 1, cache, mode,
        kv_pages=kv_pages, all_logits=True,
    )
    arr = np.asarray(logits[:n], np.float32)
    arr = mutate_point("spec.logits", arr, slot=slot)
    if not np.isfinite(arr).all():
        # Same contract as the batched-decode guard: never silently
        # argmax/sample a non-finite row (np.argmax over NaN returns
        # index 0). Signalled as ``emitted=None`` rather than raised:
        # the chunk program already consumed (donated) the caller's
        # cache arrays, so the caller MUST receive the new cache to
        # stay serviceable — a raise here would strand it on deleted
        # buffers. Callers map None to a ``nan_logits`` failure of
        # exactly this slot's request.
        return None, cache, 0, key
    if temperature <= 0.0:
        accepted, nxt = verify_greedy(arr, draft)
    else:
        accepted, nxt, key = verify_sampled(
            arr, draft, key, temperature, top_p, top_k
        )
    # One emit site covers both engines (each verify chunk routes
    # through here); rollbacks count in ``spec_rollback_tokens``.
    obs_events.emit(
        "spec_verify", slot=slot, drafted=len(draft), accepted=accepted
    )
    emitted = [int(d) for d in draft[:accepted]] + [nxt]
    return emitted, cache, accepted, key


class TreeDraft:
    """A multi-branch draft: a token trie rooted at the slot's pending
    token, flattened in insertion (DFS) order for one verify chunk.

    Node 0 is the ROOT — the pending token the engine was about to feed
    back. Nodes ``1..n-1`` are drafted continuations. Because children
    are appended after their parent, a node's storage index is always
    >= its depth, which is what makes the commit row-move strictly
    leftward (``dst <= src``) and overlap-safe.
    """

    def __init__(self, pending: int):
        self.tokens: list[int] = [int(pending)]
        self.parent: list[int] = [-1]
        self.depth: list[int] = [0]
        self._children: list[dict[int, int]] = [{}]

    def __len__(self) -> int:
        return len(self.tokens)

    @property
    def num_drafted(self) -> int:
        return len(self.tokens) - 1

    @property
    def max_depth(self) -> int:
        return max(self.depth)

    @property
    def is_chain(self) -> bool:
        """True when the trie is a single path (every node has at most
        one child) — the degenerate tree that behaves exactly like a
        linear draft."""
        return all(len(c) <= 1 for c in self._children)

    def chain_tokens(self) -> list[int]:
        """The drafted tokens of a single-path trie, root excluded —
        only meaningful when :attr:`is_chain` holds (insertion order IS
        path order for a chain)."""
        return [int(t) for t in self.tokens[1:]]

    def child(self, node: int, token: int) -> int | None:
        return self._children[node].get(int(token))

    def add_path(self, path, budget: int | None = None) -> int:
        """Insert one candidate continuation below the root, sharing
        any already-inserted prefix (siblings merge by token, so
        overlapping proposals from the radix tree, the KV tier, and the
        n-gram drafter dedup for free). Stops growing when ``budget``
        total nodes would be exceeded. Returns nodes added."""
        cur = 0
        added = 0
        for t in path:
            t = int(t)
            nxt = self._children[cur].get(t)
            if nxt is None:
                if budget is not None and len(self.tokens) >= budget:
                    break
                nxt = len(self.tokens)
                self.tokens.append(t)
                self.parent.append(cur)
                self.depth.append(self.depth[cur] + 1)
                self._children.append({})
                self._children[cur][t] = nxt
                added += 1
            cur = nxt
        return added

    def mask(self, c: int) -> np.ndarray:
        """The ``[c, c]`` additive attention bias for a ``c``-row
        chunk: row ``i`` sees column ``j`` (bias 0) iff ``j`` is an
        ancestor-of-or-equal-to ``i``; everything else gets -1e30. Pad
        rows ``i >= n`` get plain causal rows — their outputs are
        garbage either way (always rolled back), a causal row just
        keeps them shaped like ordinary prefill padding. Columns
        OUTSIDE the chunk (the committed prefix) are the caller's
        business: the model layer extends the bias with zeros there, so
        every row keeps the committed history visible."""
        n = len(self.tokens)
        m = np.full((c, c), -1e30, np.float32)
        for i in range(n):
            j = i
            while j >= 0:
                m[i, j] = 0.0
                j = self.parent[j]
        for i in range(n, c):
            m[i, : i + 1] = 0.0
        return m

    def depths(self, c: int) -> np.ndarray:
        """Per-row rope depth for a ``c``-row chunk: node ``i`` ropes
        at ``kv + depth[i]`` — the position linear decode would have
        used — so an accepted branch's KV rows are bit-identical to
        linearly-written ones and the commit can be a pure row-move.
        Pad rows rope at their storage index, same as ordinary
        prefill."""
        n = len(self.tokens)
        return np.asarray(self.depth + list(range(n, c)), np.int32)


def verify_tree_greedy(
    logits: np.ndarray, tree: TreeDraft
) -> tuple[list[int], list[int]]:
    """Greedy tree acceptance: walk from the root, at each node taking
    the TARGET's argmax for that node's prefix and descending into the
    drafted child carrying that token, if any. Every emitted token is
    the target's own argmax — bit-identical to non-speculative greedy
    decode regardless of what was drafted. Returns ``(path, emitted)``:
    the accepted node indices root-down (root excluded) and their
    tokens plus the final correction/bonus argmax."""
    preds = np.argmax(logits, axis=-1)
    path: list[int] = []
    emitted: list[int] = []
    cur = 0
    while True:
        t = int(preds[cur])
        emitted.append(t)
        nxt = tree.child(cur, t)
        if nxt is None:
            return path, emitted
        path.append(nxt)
        cur = nxt


def verify_tree_sampled(
    logits: np.ndarray,
    tree: TreeDraft,
    next_key,
    temperature: float,
    top_p: float = 1.0,
    top_k: int = 0,
) -> tuple[list[int], list[int]]:
    """Distribution-preserving tree acceptance: sample-then-match.

    At each node the TARGET token is drawn first — ``sampling.sample``
    under the node's filtered distribution with one fresh subkey from
    ``next_key()`` per EMITTED token, the exact key consumption of
    non-speculative decode — and the walk descends into the drafted
    child carrying that token, if any. Each emitted token is therefore
    an ancestral sample of the target's own filtered distribution for
    its own prefix: the emitted stream's law is EXACTLY the
    non-speculative one (no residual renormalization to get wrong) and
    it does not depend on the draft tree's shape — which is what keeps
    seeded replays bit-exact across migration even though the tree was
    built from a non-replayable source (another request's radix
    residue). The branch-acceptance probability at a node with drafted
    children ``C`` is ``sum_{c in C} p(c)`` — the multi-branch
    generalization of the linear delta-proposal accept rule. Returns
    ``(path, emitted)`` as :func:`verify_tree_greedy`."""
    path: list[int] = []
    emitted: list[int] = []
    cur = 0
    while True:
        t = int(
            sampling.sample(
                jnp.asarray(logits[cur]), next_key(), temperature, top_p, top_k
            )
        )
        emitted.append(t)
        nxt = tree.child(cur, t)
        if nxt is None:
            return path, emitted
        path.append(nxt)
        cur = nxt


def spec_verify_tree(
    model,
    cache,
    slot: int,
    tree: TreeDraft,
    kv_len: int,
    mode,
    *,
    next_key=None,
    temperature: float = 0.0,
    top_p: float = 1.0,
    top_k: int = 0,
):
    """One TREE verify of ``slot``: every trie node runs through a
    single chunked paged-prefill forward under the tree-attention mask
    and depth-rope, then the sample/argmax-then-match walk accepts one
    root path. Returns ``(emitted tokens, cache, path)``; ``emitted``
    is None on non-finite logits (same donated-cache contract as
    :func:`spec_verify_slot`, caller fails the slot as ``nan_logits``).

    The chunk writes KV for every node at ``kv + storage index`` and
    advances the slot's device kv_len past the whole chunk; the CALLER
    commits the accepted path with :func:`commit_tree_path` and then
    rolls kv_len back to ``kv + len(path) + 1`` exactly as in the
    linear path.
    """
    fault_point("spec.verify", slot=slot)
    n = len(tree)
    c = round_chunk(n)
    page = int(cache.k_pages.shape[3])
    pps = int(cache.page_table.shape[1])
    buf = np.zeros(c, np.int32)
    buf[:n] = tree.tokens
    kv_pages = gather_bucket(int(kv_len) + c, page, pps)
    logits, cache = model.prefill_paged_chunk(
        buf, slot, int(kv_len), int(kv_len) + n, n - 1, cache, mode,
        kv_pages=kv_pages, all_logits=True,
        tree_mask=tree.mask(c), tree_depth=tree.depths(c),
    )
    arr = np.asarray(logits[:n], np.float32)
    arr = mutate_point("spec.logits", arr, slot=slot)
    if not np.isfinite(arr).all():
        return None, cache, []
    if temperature <= 0.0:
        path, emitted = verify_tree_greedy(arr, tree)
    else:
        path, emitted = verify_tree_sampled(
            arr, tree, next_key, temperature, top_p, top_k
        )
    obs_events.emit(
        "spec_verify", slot=slot, drafted=tree.num_drafted,
        accepted=len(path), tree=True,
    )
    return emitted, cache, path


def commit_tree_path(cache, slot: int, kv_len: int, path: list[int]):
    """Commit an accepted root path: row-move the accepted nodes' KV
    from their DFS storage slots (``kv + node index``) to the
    contiguous positions linear decode would have written
    (``kv+1..kv+len(path)``). DFS order guarantees ``index >= depth``
    so every move is leftward; ``move_kv_rows`` skips self-moves, so a
    primary-branch (already contiguous) accept is a no-op. The caller
    still owns the kv_len rollback afterwards."""
    from triton_distributed_tpu.models.paged_kv_cache import move_kv_rows

    src = [int(kv_len) + int(i) for i in path]
    dst = [int(kv_len) + j for j in range(1, len(path) + 1)]
    return move_kv_rows(cache, slot, src, dst)
