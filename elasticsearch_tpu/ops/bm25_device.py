"""Jitted BM25 query execution over tiled device postings.

This replaces the reference's shard-local scoring hot loop —
`ContextIndexSearcher.searchLeaf` → `weight.bulkScorer(ctx)` →
`bulkScorer.score(leafCollector, liveDocs)` (server/src/main/java/org/
elasticsearch/search/internal/ContextIndexSearcher.java:170-206) plus the
top-k heap of `TopDocsCollectorContext` (search/query/
TopDocsCollectorContext.java:68) — with one XLA program:

    gather posting tiles → BM25 contributions → scatter-add dense scores
    → combine boolean clause masks → masked `lax.top_k`

Where Lucene iterates doc-at-a-time per segment per term with a heap, the
TPU scores *all* postings of *all* query terms at once: the [T, MT, TILE]
gather feeds the VPU elementwise BM25 expression and a dense scatter; top-k
is a single `lax.top_k` whose tie-break (lower index wins) matches Lucene's
TopScoreDocCollector doc-id tie-break exactly.

A query is compiled (host side, see query/compile.py) into:
- a hashable static `spec` (nested tuples describing the operator tree);
- a pytree of per-node `arrays` (tile ids, spans, fp32 term weights, the
  256-entry norm-inverse cache — exactly Lucene's per-query cache).
`execute` is jitted with the spec static, so queries with the same shape
bucket share one compilation.

Scoring math is bit-identical to ops/bm25.py (the Lucene-parity oracle):
fp32 `w - w / (1 + tf * cache[normByte])` with host-precomputed fp32 `w`.

Boolean semantics follow the reference's BooleanQuery:
- must/should contribute scores; filter/must_not never do;
- a bool with no must/filter requires ≥1 should (minimum_should_match
  default), otherwise shoulds are optional;
- constant-score leaves (range, exists, match_all) score `boost` per hit.
"""

from __future__ import annotations

from functools import partial
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from ..index.tiles import TILE

NEG_INF = float("-inf")

# ---------------------------------------------------------------------------
# Plan representation
#
# spec (static, hashable):
#   ("terms", field_name, NT)             — weighted term disjunction
#   ("terms_const", field_name, NT)       — same, constant-score (filters)
#   ("range", field_name)                 — numeric range (bounds in arrays)
#   ("exists", field_name, field_kind)    — docs with a value for the field
#   ("const", child_spec)                 — constant_score wrapper
#   ("match_all",)                        — every live doc, constant score
#   ("match_none",)                       — no doc
#   ("cached_mask", slot)                 — filter-cache plane: matched =
#       seg["masks"][slot], a device-resident bool[N] evaluated once by
#       compute_filter_mask and reused across requests (filter cache,
#       index/filter_cache.py)
#   ("bool", (must...), (should...), (filter...), (must_not...), msm, lead)
#       msm: minimum_should_match (int; -1 = default rule)
#       lead: index of the single-span constant FILTER clause that drives
#       sparse candidate generation (compile-time selectivity choice, the
#       ConjunctionDISI lead-iterator analog), or -1 for the default
#       must-driven fold
#
# A terms node is a FLAT TILE WORKLIST: one entry per posting tile touched
# by any query term, padded to the pow-2 bucket NT. Each entry carries its
# term's posting span and precomputed fp32 weight, so the kernel's shape
# depends only on the total number of tiles — not on term count or on the
# per-term maximum — which keeps the set of compiled shapes tiny (one per
# pow-2 worklist size) and the gather dense. Work scales with postings
# actually touched, like Lucene's per-term postings iteration, but batched.
#
# arrays (pytree), by node type:
#   terms:       {"tile_ids": i32[NT], "starts": i32[NT], "ends": i32[NT],
#                 "weights": f32[NT], "cache": f32[256]}
#   terms_const: {"tile_ids": i32[NT], "starts": i32[NT], "ends": i32[NT],
#                 "boost": f32[]}
#   range:       {"lo": f32[], "hi": f32[], "boost": f32[]}  (NaN-safe)
#   exists:      {"boost": f32[]}
#   const:       {"boost": f32[], "child": <child arrays>}
#   match_all:   {"boost": f32[]}
#   match_none:  {}
#   bool:        {"boost": f32[], "children": (child arrays in
#                 must+should+filter+must_not order)}
# ---------------------------------------------------------------------------


def _eval_node(spec, arrays, seg: dict[str, Any], num_docs: int):
    """Returns (scores f32[num_docs], matched bool[num_docs])."""
    kind = spec[0]
    if kind == "terms":
        return _eval_terms(spec, arrays, seg, num_docs)
    if kind == "terms_gather":
        return _eval_terms_gather(spec, arrays, seg, num_docs)
    if kind == "terms_const":
        matched = _terms_matched(spec, arrays, seg, num_docs)
        scores = jnp.where(matched, arrays["boost"], jnp.float32(0.0))
        return scores, matched
    if kind == "const":
        _, child_spec = spec
        _, matched = _eval_node(child_spec, arrays["child"], seg, num_docs)
        scores = jnp.where(matched, arrays["boost"], jnp.float32(0.0))
        return scores, matched
    if kind == "exists":
        _, field_name, field_kind = spec
        if field_kind == "inverted":
            matched = seg["fields"][field_name][4]  # presence bitmap
        else:
            matched = ~jnp.isnan(seg["doc_values"][field_name])
        scores = jnp.where(matched, arrays["boost"], jnp.float32(0.0))
        return scores, matched
    if kind == "range":
        return _eval_range(spec, arrays, seg, num_docs)
    if kind == "geo_distance":
        _, field_name = spec
        lat = seg["doc_values"][field_name + ".lat"]
        lon = seg["doc_values"][field_name + ".lon"]
        d = _haversine_m(jnp, lat, lon, arrays["lat"], arrays["lon"])
        matched = ~jnp.isnan(lat) & (d <= arrays["radius_m"])
        scores = jnp.where(matched, arrays["boost"], jnp.float32(0.0))
        return scores, matched
    if kind == "geo_box":
        _, field_name = spec
        lat = seg["doc_values"][field_name + ".lat"]
        lon = seg["doc_values"][field_name + ".lon"]
        in_lat = (lat <= arrays["top"]) & (lat >= arrays["bottom"])
        # Antimeridian-crossing boxes: left > right wraps.
        wraps = arrays["left"] > arrays["right"]
        in_lon_plain = (lon >= arrays["left"]) & (lon <= arrays["right"])
        in_lon_wrap = (lon >= arrays["left"]) | (lon <= arrays["right"])
        in_lon = jnp.where(wraps, in_lon_wrap, in_lon_plain)
        matched = ~jnp.isnan(lat) & in_lat & in_lon
        scores = jnp.where(matched, arrays["boost"], jnp.float32(0.0))
        return scores, matched
    if kind == "rank_feature":
        _, field_name, fn = spec
        col = seg["doc_values"][field_name]
        matched = ~jnp.isnan(col)
        v = jnp.where(matched, col, jnp.float32(0.0))
        if fn == "saturation":
            s = v / (v + arrays["pivot"])
        elif fn == "log":
            s = jnp.log(arrays["scaling"] + v)
        else:  # sigmoid
            ve = v ** arrays["exponent"]
            s = ve / (ve + arrays["pivot"] ** arrays["exponent"])
        scores = jnp.where(matched, arrays["boost"] * s, jnp.float32(0.0))
        return scores, matched
    if kind == "cached_mask":
        # A filter-cache plane (index/filter_cache.py): the subtree's
        # matched set was evaluated once and parked in HBM; the node is
        # a plain read of seg["masks"][slot]. Bit-identical to
        # re-evaluating the original filter subtree by construction (the
        # plane IS that evaluation), so substitution never moves top-k,
        # scores, or totals. Filter context discards scores, but the
        # node still reports boost-where-matched like every constant
        # leaf so a (never-produced) scoring placement would not differ.
        matched = seg["masks"][spec[1]]
        scores = jnp.where(matched, arrays["boost"], jnp.float32(0.0))
        return scores, matched
    if kind == "match_all":
        matched = jnp.ones(num_docs, dtype=bool)
        scores = jnp.full(num_docs, arrays["boost"], dtype=jnp.float32)
        return scores, matched
    if kind == "match_none":
        return (
            jnp.zeros(num_docs, dtype=jnp.float32),
            jnp.zeros(num_docs, dtype=bool),
        )
    if kind == "bool":
        return _eval_bool(spec, arrays, seg, num_docs)
    if kind == "boosting":
        _, pos_spec, neg_spec = spec
        ps, pm = _eval_node(pos_spec, arrays["positive"], seg, num_docs)
        _, nm = _eval_node(neg_spec, arrays["negative"], seg, num_docs)
        # BoostingQueryBuilder: negative matches are demoted, not excluded.
        factor = jnp.where(nm, arrays["negative_boost"], jnp.float32(1.0))
        scores = jnp.where(pm, ps * factor * arrays["boost"], jnp.float32(0.0))
        return scores, pm
    if kind == "terms_set":
        return _eval_terms_set(spec, arrays, seg, num_docs)
    if kind == "nested":
        return _eval_nested(spec, arrays, seg, num_docs)
    if kind == "script":
        return _eval_script(spec, arrays, seg, num_docs)
    if kind == "function_score":
        return _eval_function_score(spec, arrays, seg, num_docs)
    if kind == "phrase":
        return _eval_phrase(spec, arrays, seg, num_docs)
    if kind == "span_near":
        return _eval_span_near(spec, arrays, seg, num_docs)
    if kind == "span_not":
        return _eval_span_not(spec, arrays, seg, num_docs)
    if kind == "doc_set":
        docs = arrays["docs"]  # i32[ND], -1 padding
        idx = jnp.where(docs >= 0, docs, num_docs)
        matched = (
            jnp.zeros(num_docs + 1, dtype=bool).at[idx].max(docs >= 0)[:num_docs]
        )
        scores = jnp.where(matched, arrays["boost"], jnp.float32(0.0))
        return scores, matched
    if kind == "dismax":
        _, child_specs = spec
        best = jnp.full(num_docs, jnp.float32(0.0))
        total = jnp.zeros(num_docs, dtype=jnp.float32)
        matched = jnp.zeros(num_docs, dtype=bool)
        for child_spec, child_arrays in zip(child_specs, arrays["children"]):
            s, m = _eval_node(child_spec, child_arrays, seg, num_docs)
            s = jnp.where(m, s, jnp.float32(0.0))
            best = jnp.maximum(best, s)
            total = total + s
            matched = matched | m
        tie = arrays["tie"]
        # NOTE: XLA may contract this mul+add into an FMA (it even clones
        # the multiply past an optimization_barrier), so dis_max scores can
        # differ from the oracle's two-rounding result by 1 ulp. Ranking
        # parity (ids + order) is unaffected in practice; the parity
        # contract for fused expressions is ids/order exact, scores within
        # ulps (BENCH gate).
        scores = best + tie * (total - best)
        scores = jnp.where(matched, scores * arrays["boost"], jnp.float32(0.0))
        return scores, matched
    raise ValueError(f"unknown plan node kind [{kind}]")


def _eval_terms_set(spec, arrays, seg, num_docs):
    """terms_set: BM25-sum scoring gated on per-doc term coverage.

    The scored child is the plain terms disjunction; coverage counts come
    from one matched-only worklist per term (CoveringQuery's per-clause
    DISI count, all clauses at once); the per-doc requirement reads a
    doc-values column or evaluates a painless-lite expression inline.
    Requirements clamp to >= 1 and NaN (missing value) never matches.
    Ref: TermsSetQueryBuilder -> lucene CoveringQuery.
    """
    _, scored_spec, count_specs, msm_kind, msm_ref = spec
    s, _m = _eval_node(scored_spec, arrays["scored"], seg, num_docs)
    count = jnp.zeros(num_docs, dtype=jnp.float32)
    for cspec, carr in zip(count_specs, arrays["counts"]):
        _, m = _eval_node(cspec, carr, seg, num_docs)
        count = count + m.astype(jnp.float32)
    if msm_kind == "field":
        required = seg["doc_values"][msm_ref]
    else:
        from ..script import compile_script

        source, _names = msm_ref
        required = jnp.asarray(
            compile_script(source).evaluate(
                jnp,
                jnp.zeros(num_docs, dtype=jnp.float32),
                seg["doc_values"],
                seg.get("vectors", {}),
                arrays["params"],
            ),
            dtype=jnp.float32,
        )
        required = jnp.broadcast_to(required, (num_docs,))
    required = jnp.maximum(required, jnp.float32(1.0))  # NaN propagates
    matched = count >= required  # NaN requirement compares False
    scores = jnp.where(matched, s * arrays["boost"], jnp.float32(0.0))
    return scores, matched


def _eval_nested(spec, arrays, seg, num_docs):
    """Nested query: child runs in the path's own document space, then the
    child-doc results JOIN to parents with one scatter per reduction.

    The TPU form of the reference's block join (NestedQueryBuilder.java:54
    lowering to ToParentBlockJoinQuery): where Lucene walks each parent's
    contiguous child range against a parent bitset, here every nested doc
    of the whole segment scores at once and `parent_of` scatters matches
    and score reductions (sum/avg/max/min per score_mode) into parent
    space. Unmatched parents score 0; `none` joins matches only.
    """
    _, path, child_spec, score_mode = spec
    nblk = seg["nested"][path]
    ntree = nblk["tree"]
    nn = ntree["live"].shape[0]
    cs, cm = _eval_node(child_spec, arrays["child"], ntree, nn)
    cm = cm & ntree["live"]
    cs = jnp.where(cm, cs, jnp.float32(0.0))
    parent_of = nblk["parent_of"]  # i32[nn]
    idx = jnp.where(cm, parent_of, jnp.int32(num_docs))  # sentinel slot
    matched = jnp.zeros(num_docs + 1, dtype=bool).at[idx].max(cm)[:num_docs]
    if score_mode == "none":
        # Lucene ToParentBlockJoinQuery ScoreMode.None: parents match with
        # score 0 (boost * 0 stays 0, as in the reference).
        return jnp.zeros(num_docs, dtype=jnp.float32), matched
    if score_mode in ("sum", "avg"):
        sums = (
            jnp.zeros(num_docs + 1, dtype=jnp.float32).at[idx].add(cs)[:num_docs]
        )
        if score_mode == "avg":
            counts = (
                jnp.zeros(num_docs + 1, dtype=jnp.float32)
                .at[idx]
                .add(cm.astype(jnp.float32))[:num_docs]
            )
            sums = sums / jnp.maximum(counts, jnp.float32(1.0))
        reduced = sums
    elif score_mode in ("max", "min"):
        sign = jnp.float32(1.0 if score_mode == "max" else -1.0)
        best = (
            jnp.full(num_docs + 1, NEG_INF, dtype=jnp.float32)
            .at[idx]
            .max(jnp.where(cm, sign * cs, jnp.float32(NEG_INF)))[:num_docs]
        )
        reduced = jnp.where(matched, sign * best, jnp.float32(0.0))
    else:
        raise ValueError(f"unknown nested score_mode [{score_mode}]")
    scores = jnp.where(matched, reduced * arrays["boost"], jnp.float32(0.0))
    return scores, matched


def _haversine_m(xp, lat, lon, qlat, qlon):
    """Great-circle distance in meters (GeoUtils.arcDistance; f32 on the
    VPU — sub-meter accuracy is not the contract, matching ES's own
    Haversin approximation)."""
    rad = 0.017453292519943295
    phi1 = lat * rad
    phi2 = qlat * rad
    dphi = (qlat - lat) * rad
    dlmb = (qlon - lon) * rad
    a = (
        xp.sin(dphi / 2) ** 2
        + xp.cos(phi1) * xp.cos(phi2) * xp.sin(dlmb / 2) ** 2
    )
    return 6371008.7714 * 2 * xp.arctan2(xp.sqrt(a), xp.sqrt(1 - a))


def _eval_script(spec, arrays, seg, num_docs):
    """script_score: replace the child's score with a traced expression.

    The painless-lite script evaluates as jnp array ops over ALL docs at
    once (compilation happens at trace time, so the expression fuses into
    the surrounding XLA program; x-pack vector functions become matmuls on
    the MXU)."""
    from ..script import compile_script

    _, child_spec, source, _param_names, has_min_score = spec
    child_scores, matched = _eval_node(child_spec, arrays["child"], seg, num_docs)
    script = compile_script(source)
    result = script.evaluate(
        jnp,
        child_scores,
        seg["doc_values"],
        seg.get("vectors", {}),
        arrays["params"],
    )
    result = jnp.broadcast_to(
        jnp.asarray(result, dtype=jnp.float32), (num_docs,)
    )
    scores = jnp.where(matched, result * arrays["boost"], jnp.float32(0.0))
    if has_min_score:
        matched = matched & (scores >= arrays["min_score"])
        scores = jnp.where(matched, scores, jnp.float32(0.0))
    return scores, matched


def _eval_function_score(spec, arrays, seg, num_docs):
    """function_score: modify the child's scores with filtered functions.

    All math lives in query/functions.py (shared with the numpy oracle so
    fp32 rounding matches); this evaluator supplies the traced context —
    doc-value columns, the child pass, per-function filter masks. The
    whole thing fuses into the surrounding XLA program: fvf/decay are
    VPU elementwise chains over doc-values planes, script functions may
    lower to MXU matmuls (vector ops). Ref: FunctionScoreQueryBuilder.
    """
    from ..query.functions import combine_function_score, eval_function

    (_, child_spec, fspecs, filter_specs, score_mode, boost_mode, has_min) = spec
    child_scores, matched = _eval_node(child_spec, arrays["child"], seg, num_docs)
    values, applies, weights = [], [], []
    for fspec, farrays, fil_spec, fil_arrays in zip(
        fspecs, arrays["functions"], filter_specs, arrays["filters"]
    ):
        values.append(
            eval_function(
                jnp,
                fspec,
                farrays,
                num_docs=num_docs,
                column=lambda name: seg["doc_values"].get(name),
                child_scores=child_scores,
                doc_values=seg["doc_values"],
                vectors=seg.get("vectors", {}),
            )
        )
        if fil_spec is None:
            applies.append(matched)
        else:
            _, fil_matched = _eval_node(fil_spec, fil_arrays, seg, num_docs)
            applies.append(matched & fil_matched)
        weights.append(farrays["weight"])
    return combine_function_score(
        jnp,
        child_scores=child_scores,
        matched=matched,
        values=values,
        applies=applies,
        weights=weights,
        score_mode=score_mode,
        boost_mode=boost_mode,
        max_boost=arrays["max_boost"],
        boost=arrays["boost"],
        min_score=arrays["min_score"] if has_min else None,
    )


def _gather_tiles(spec, arrays, seg, want: str = "tn"):
    """Shared worklist gather: (docs, vals, valid), each [NT, S].

    `want` picks the value plane: "tn" (precomputed impact, the fast path)
    or "tf" (raw frequency, for the custom-params gather kernel).
    """
    field_name = spec[1]
    doc_tiles, tn_tiles, tf_tiles, norm_bytes, _present = seg["fields"][field_name]
    tile_ids = arrays["tile_ids"]  # i32[NT]
    starts = arrays["starts"]  # i32[NT] (term's span, same for its tiles)
    ends = arrays["ends"]  # i32[NT]
    docs = doc_tiles[tile_ids]  # i32[NT, S]
    vals = (tn_tiles if want == "tn" else tf_tiles)[tile_ids]  # f32[NT, S]
    pos = tile_ids[:, None] * TILE + jnp.arange(TILE, dtype=jnp.int32)
    valid = (pos >= starts[:, None]) & (pos < ends[:, None])
    return docs, vals, valid, norm_bytes


def _scatter_scored(docs, contrib, valid, num_docs):
    idx = jnp.where(valid, docs, num_docs)  # sentinel slot = num_docs
    scores = (
        jnp.zeros(num_docs + 1, dtype=jnp.float32)
        .at[idx]
        .add(jnp.where(valid, contrib, jnp.float32(0.0)))[:num_docs]
    )
    matched = (
        jnp.zeros(num_docs + 1, dtype=bool).at[idx].max(valid)[:num_docs]
    )
    return scores, matched


def _eval_terms(spec, arrays, seg, num_docs):
    """Fast path: per-posting impacts precomputed, zero gathers in-loop."""
    docs, tn, valid, _norm = _gather_tiles(spec, arrays, seg, want="tn")
    w = arrays["weights"][:, None]  # f32[NT, 1] per-tile term weight
    one = jnp.float32(1.0)
    contrib = w - w / (one + tn)
    return _scatter_scored(docs, contrib, valid, num_docs)


def _eval_terms_gather(spec, arrays, seg, num_docs):
    """Fallback for non-default k1/b or statistics scope: per-doc norm via
    the 256-entry cache (Lucene's per-query cache), costing a gather."""
    docs, tfs, valid, norm_bytes = _gather_tiles(spec, arrays, seg, want="tf")
    cache = arrays["cache"]  # f32[256]
    ninv = cache[norm_bytes[docs]]  # f32[NT, S]
    w = arrays["weights"][:, None]
    one = jnp.float32(1.0)
    contrib = w - w / (one + tfs * ninv)
    return _scatter_scored(docs, contrib, valid, num_docs)


def _eval_phrase(spec, arrays, seg, num_docs):
    """Exact-phrase evaluation over the segment's position planes.

    The TPU replacement for Lucene's ExactPhraseMatcher doc-at-a-time
    postings zipper (reference: MatchPhraseQueryBuilder.java:28 lowering to
    PhraseQuery): instead of advancing m positional iterators in lockstep,
    every position entry of every phrase slot is gathered at once, each
    shifted to its phrase-aligned position (apos = pos - slot offset), and
    sorted by (doc, apos). A full phrase occurrence at (doc, apos) produces
    exactly n_slots equal keys — one per slot, since one position holds one
    token — so occurrences are runs of length n_slots, counted with a
    static shifted-compare fold exactly like the sparse BM25 kernel's
    run-sum. Phrase frequency then scores through the standard BM25
    expression with the summed-idf weight (Lucene PhraseWeight +
    BM25Similarity over combined termStatistics).
    """
    _, field_name, nt, n_slots = spec
    pos_doc_tiles, pos_val_tiles = seg["positions"][field_name]
    norm_bytes = seg["fields"][field_name][3]
    tile_ids = arrays["tile_ids"]  # i32[NT]
    docs = pos_doc_tiles[tile_ids]  # i32[NT, S]
    poss = pos_val_tiles[tile_ids]  # i32[NT, S]
    pos_idx = tile_ids[:, None] * TILE + jnp.arange(TILE, dtype=jnp.int32)
    valid = (pos_idx >= arrays["starts"][:, None]) & (
        pos_idx < arrays["ends"][:, None]
    )
    apos = poss - arrays["shifts"][:, None]
    valid &= apos >= 0
    sentinel = jnp.int32(num_docs)
    doc_key = jnp.where(valid, docs, sentinel).reshape(-1)  # [P]
    apos_key = jnp.where(valid, apos, jnp.int32(-1)).reshape(-1)
    p = doc_key.shape[0]
    d_s, a_s = jax.lax.sort((doc_key, apos_key), num_keys=2, is_stable=False)
    # Run detection: occurrence ⇔ n_slots consecutive equal (doc, apos).
    d_ext = jnp.concatenate(
        [d_s, jnp.full(n_slots, num_docs + 1, dtype=d_s.dtype)]
    )
    a_ext = jnp.concatenate([a_s, jnp.full(n_slots, -2, dtype=a_s.dtype)])
    full = jnp.ones(p, dtype=bool)
    for j in range(1, n_slots):
        full &= (d_ext[j : j + p] == d_s) & (a_ext[j : j + p] == a_s)
    is_start = jnp.concatenate(
        [jnp.ones(1, dtype=bool), (d_s[1:] != d_s[:-1]) | (a_s[1:] != a_s[:-1])]
    )
    occurrence = full & is_start & (d_s != sentinel)
    freq_idx = jnp.where(occurrence, d_s, sentinel)
    freq = (
        jnp.zeros(num_docs + 1, dtype=jnp.float32)
        .at[freq_idx]
        .add(occurrence.astype(jnp.float32))[:num_docs]
    )
    matched = freq > 0
    ninv = arrays["cache"][norm_bytes[:num_docs]]
    w = arrays["weight"]
    scores = w - w / (jnp.float32(1.0) + freq * ninv)
    scores = jnp.where(matched, scores, jnp.float32(0.0))
    return scores, matched


def _segmented_cummax(seg_ids, vals):
    """Inclusive per-segment running max (segments = equal seg_ids runs).

    The classic segmented-scan combine is associative, so it lowers to
    XLA's log-depth associative_scan rather than a sequential loop.
    """

    def combine(a, b):
        ia, va = a
        ib, vb = b
        return ib, jnp.where(ia == ib, jnp.maximum(va, vb), vb)

    _, out = jax.lax.associative_scan(combine, (seg_ids, vals))
    return out


def _gather_span_events(arrays, seg, field_name, num_docs):
    """Flatten + sort a positions worklist to (doc, pos, clause) events.

    Shared by the span kernels: the unit-span form of the phrase kernel's
    gather — every position occurrence of every clause term, sorted by
    (doc, pos, clause); invalid slots carry doc = num_docs (sentinel)."""
    pos_doc_tiles, pos_val_tiles = seg["positions"][field_name]
    tile_ids = arrays["tile_ids"]  # i32[NT]
    docs = pos_doc_tiles[tile_ids]  # i32[NT, S]
    poss = pos_val_tiles[tile_ids]  # i32[NT, S]
    pos_idx = tile_ids[:, None] * TILE + jnp.arange(TILE, dtype=jnp.int32)
    valid = (pos_idx >= arrays["starts"][:, None]) & (
        pos_idx < arrays["ends"][:, None]
    )
    clause = jnp.broadcast_to(arrays["clause_of"][:, None], docs.shape)
    sentinel = jnp.int32(num_docs)
    doc_key = jnp.where(valid, docs, sentinel).reshape(-1)
    pos_key = jnp.where(valid, poss, jnp.int32(2**30)).reshape(-1)
    clause_key = jnp.where(valid, clause, jnp.int32(0)).reshape(-1)
    return jax.lax.sort((doc_key, pos_key, clause_key), num_keys=3)


def _span_chain_ends(d_s, p_s, c_s, n_clauses: int, slop: int):
    """Events that END an ordered chain c0 < c1 < ... < c{n-1} with total
    stretch <= slop. dp[l] at an event of clause l = the LARGEST reachable
    chain start p0 (greedy max-start is optimal: the slop constraint only
    involves p0 and the end position)."""
    neg = jnp.float32(-(2.0**31))
    pf = p_s.astype(jnp.float32)
    dp = jnp.where(c_s == 0, pf, neg)
    idx = jnp.arange(d_s.shape[0], dtype=jnp.int32)
    # First index of each (doc, pos) group, for STRICT pos ordering.
    is_new = jnp.concatenate(
        [
            jnp.ones(1, dtype=bool),
            (d_s[1:] != d_s[:-1]) | (p_s[1:] != p_s[:-1]),
        ]
    )
    group_start = jax.lax.associative_scan(
        jnp.maximum, jnp.where(is_new, idx, jnp.int32(-1))
    )
    prev_idx = jnp.maximum(group_start - 1, 0)
    has_prev = (group_start > 0) & (d_s[prev_idx] == d_s)
    for level in range(1, n_clauses):
        vals = jnp.where(c_s == level - 1, dp, neg)
        run = _segmented_cummax(d_s, vals)
        carry = jnp.where(has_prev, run[prev_idx], neg)
        dp = jnp.where(c_s == level, carry, neg)
    ok = (c_s == n_clauses - 1) & (dp > neg)
    stretch = pf - dp - jnp.float32(n_clauses - 1)
    return ok & (stretch <= jnp.float32(slop))


def _span_freq_scores(seg, field_name, d_s, ok, weight, cache, num_docs):
    """Occurrence count -> BM25, exactly the phrase kernel's scoring."""
    sentinel = jnp.int32(num_docs)
    freq_idx = jnp.where(ok & (d_s != sentinel), d_s, sentinel)
    freq = (
        jnp.zeros(num_docs + 1, dtype=jnp.float32)
        .at[freq_idx]
        .add((ok & (d_s != sentinel)).astype(jnp.float32))[:num_docs]
    )
    matched = freq > 0
    norm_bytes = seg["fields"][field_name][3]
    ninv = cache[norm_bytes[:num_docs]]
    w = weight
    scores = w - w / (jnp.float32(1.0) + freq * ninv)
    scores = jnp.where(matched, scores, jnp.float32(0.0))
    return scores, matched


def _eval_span_near(spec, arrays, seg, num_docs):
    """span_near / span_or / span_first over unit spans.

    The TPU form of Lucene's NearSpansOrdered/Unordered zipper
    (SpanNearQueryBuilder): all clause positions gather at once, a
    log-depth segmented-scan DP finds chain ends, and occurrences scatter
    to per-doc frequencies. Matching sets are exact for unit-span clauses;
    scoring uses freq = chain-end count with the summed-idf weight (the
    reference's SloppySimScorer weights each span 1/(1+stretch) — a
    scoring refinement over the same matched set, noted divergence).
    """
    _, field_name, _nt, n_clauses, slop, ordered, end_limit = spec
    d_s, p_s, c_s = _gather_span_events(arrays, seg, field_name, num_docs)
    ok = _span_chain_ends(d_s, p_s, c_s, n_clauses, slop)
    if not ordered and n_clauses == 2:
        ok = ok | _span_chain_ends(
            d_s, p_s, jnp.int32(1) - c_s, n_clauses, slop
        )
    if end_limit >= 0:
        ok = ok & (p_s + 1 <= jnp.int32(end_limit))
    return _span_freq_scores(
        seg, field_name, d_s, ok, arrays["weight"], arrays["cache"], num_docs
    )


def _eval_span_not(spec, arrays, seg, num_docs):
    """span_not over unit spans: include positions with no exclude
    position in [p-pre, p+post] (SpanNotQueryBuilder). Clause 0 =
    include, clause 1 = exclude; violation checks are two segmented scans
    (nearest exclude at-or-before from the left, at-or-after from the
    right)."""
    _, field_name, _nt, pre, post = spec
    d_s, p_s, c_s = _gather_span_events(arrays, seg, field_name, num_docs)
    pf = p_s.astype(jnp.float32)
    neg = jnp.float32(-(2.0**31))
    # Nearest exclude position <= p (inclusive scan; same-(doc,pos)
    # excludes sort after includes but are caught by the backward scan).
    before = _segmented_cummax(d_s, jnp.where(c_s == 1, pf, neg))
    # Nearest exclude position >= p: reverse, negate, scan, undo.
    after = -_segmented_cummax(
        d_s[::-1], jnp.where(c_s[::-1] == 1, -pf[::-1], neg)
    )[::-1]
    violated = (before >= pf - jnp.float32(pre)) | (
        after <= pf + jnp.float32(post)
    )
    ok = (c_s == 0) & ~violated
    return _span_freq_scores(
        seg, field_name, d_s, ok, arrays["weight"], arrays["cache"], num_docs
    )


def _terms_matched(spec, arrays, seg, num_docs):
    docs, _vals, valid, _norm = _gather_tiles(spec, arrays, seg)
    idx = jnp.where(valid, docs, num_docs)
    return jnp.zeros(num_docs + 1, dtype=bool).at[idx].max(valid)[:num_docs]


def _eval_range(spec, arrays, seg, num_docs):
    _, field_name = spec
    col = seg["doc_values"][field_name]  # f32[N], NaN = missing
    matched = (col >= arrays["lo"]) & (col <= arrays["hi"])  # NaN compares False
    scores = jnp.where(matched, arrays["boost"], jnp.float32(0.0))
    return scores, matched


def _eval_bool(spec, arrays, seg, num_docs):
    # spec[6] (the sparse lead-clause choice) is irrelevant dense-side.
    must_s, should_s, filter_s, must_not_s, msm = spec[1:6]
    children = arrays["children"]
    i = 0
    must, should, filt, must_not = [], [], [], []
    for group, out in (
        (must_s, must),
        (should_s, should),
        (filter_s, filt),
        (must_not_s, must_not),
    ):
        for child_spec in group:
            out.append(_eval_node(child_spec, children[i], seg, num_docs))
            i += 1

    matched = jnp.ones(num_docs, dtype=bool)
    for _, m in must:
        matched &= m
    for _, m in filt:
        matched &= m
    for _, m in must_not:
        matched &= ~m

    effective_msm = msm
    if effective_msm < 0:  # default: 1 iff no must and no filter clauses
        effective_msm = 1 if (not must_s and not filter_s) else 0
    if should:
        if effective_msm == 1:
            any_should = jnp.zeros(num_docs, dtype=bool)
            for _, m in should:
                any_should |= m
            matched &= any_should
        elif effective_msm > 1:
            n_should = jnp.zeros(num_docs, dtype=jnp.int32)
            for _, m in should:
                n_should += m.astype(jnp.int32)
            matched &= n_should >= effective_msm

    score = jnp.zeros(num_docs, dtype=jnp.float32)
    for s, _ in must:
        score = score + s
    for s, _ in should:
        score = score + s
    score = jnp.where(matched, score * arrays["boost"], jnp.float32(0.0))
    return score, matched


def _execute_inner(seg, spec, arrays, k: int, bounds=None):
    live = seg["live"]
    num_docs = live.shape[0]
    scores, matched = _eval_node(spec, arrays, seg, num_docs)
    eligible = matched & live
    if bounds is not None:
        # Packed multi-tenant plane: only this lane's tenant doc range is
        # eligible — cross-tenant docs can never enter the top-k.
        iota = jnp.arange(num_docs, dtype=jnp.int32)
        eligible &= (iota >= bounds[0]) & (iota < bounds[1])
    masked = jnp.where(eligible, scores, jnp.float32(NEG_INF))
    kk = min(k, num_docs)
    top_scores, top_ids = jax.lax.top_k(masked, kk)
    total = jnp.sum(eligible, dtype=jnp.int32)
    return top_scores, top_ids.astype(jnp.int32), total


# ---------------------------------------------------------------------------
# Sparse (candidate-centric) execution for pure term-disjunction specs.
#
# The dense path scatter-adds into an [N] score vector (TPU scatter is slow —
# ~66M updates/s measured — and top_k over [Q, N] scales with corpus size).
# For the hot query shape — a `terms` disjunction, i.e. every match query —
# the candidate-centric kernel instead:
#
#   1. gathers the worklist tiles -> (doc, contrib) pairs [P], P = NT*TILE;
#   2. STABLY sorts pairs by doc id (stability keeps same-doc entries in
#      worklist order = query-term order);
#   3. sums each doc-run with T_pad static shifted adds — a LEFT FOLD in
#      term order, reproducing the oracle's (and the reference's per-term
#      BulkScorer accumulation, ContextIndexSearcher.java:170-206) fp32
#      rounding exactly;
#   4. takes top-k over the run heads: positions ascend by doc id, so
#      lax.top_k's lowest-index tie-break IS Lucene's doc-id tie-break.
#
# Work scales with postings touched (like Lucene's term iteration), not with
# corpus size — the property that lets one chip hold its ground at 10M docs.
# ---------------------------------------------------------------------------


# Widest disjunction the run-fold unrolls (the fold is t_pad-1 static
# shifted adds; wider disjunctions route to the dense kernel). Rationale:
# README "Conjunction execution".
SPARSE_TPAD_MAX = 32

# `jax.named_scope` names of the sparse kernel's phases. A profiler trace
# carries them in each device op's name metadata, so device time splits
# by phase: scoring (worklist gather, doc sort, run fold, filters), the
# top-k over candidates, and the total-hits count.
SCOPE_SCORE = "bm25.score"
SCOPE_TOPK = "bm25.topk"
SCOPE_TOTAL = "bm25.total"


def supports_sparse(spec) -> bool:
    """Sparse execution covers precomputed-impact term disjunctions with a
    bounded run-fold length (wider disjunctions route to the dense kernel),
    and bool conjunctions of one such disjunction with constant-score term
    filters/exclusions — the BASELINE config-3 shape. Candidate-centric
    execution beats the dense path because top-k runs over the candidate
    worklist, never over a [num_docs] plane."""
    if spec[0] == "terms":
        return spec[3] <= SPARSE_TPAD_MAX
    if spec[0] == "bool":
        must_s, should_s, filter_s, must_not_s = spec[1:5]
        # cached_mask clauses (filter-cache planes) verify at candidates
        # with ONE gather — cheaper than either membership primitive.
        const_kinds = ("terms_const", "cached_mask")
        return (
            len(must_s) == 1
            and must_s[0][0] == "terms"
            and must_s[0][3] <= SPARSE_TPAD_MAX
            and not should_s
            and all(c[0] in const_kinds for c in filter_s)
            and all(c[0] in const_kinds for c in must_not_s)
        )
    return False


def _bool_lead(spec) -> int:
    """The compile-time lead-clause choice of a bool spec (-1 = the
    default must-driven fold)."""
    return spec[6] if len(spec) > 6 else -1


def _sparse_inner(seg, spec, arrays, k: int, bounds=None):
    """Candidate-centric top-k for a supports_sparse spec."""
    if spec[0] == "bool":
        if _bool_lead(spec) >= 0:
            return _sparse_lead_inner(seg, spec, arrays, k, bounds=bounds)
        return _sparse_bool_inner(seg, spec, arrays, k, bounds=bounds)
    return _sparse_terms_inner(seg, spec, arrays, k, bounds=bounds)


def _const_membership(seg, child_spec, carr, safe_docs, num_docs):
    """Constant-clause membership test at candidate docs: a cached
    filter-mask plane gathers directly (zero posting work), binary search
    for single contiguous spans (O(P log df), no [N]-sized scatter), the
    dense presence bitmap gathered at candidates otherwise."""
    if child_spec[0] == "cached_mask":
        return seg["masks"][child_spec[1]][safe_docs]
    if len(child_spec) == 4 and child_spec[3] == 1:
        return _span_member(
            seg, child_spec[1], carr["span_start"], carr["span_end"],
            safe_docs,
        )
    return _terms_matched(child_spec, carr, seg, num_docs)[safe_docs]


def _sparse_bool_inner(seg, spec, arrays, k: int, bounds=None):
    """bool(must=[terms], filter/must_not=[terms_const...]) without any
    [num_docs]-sized score plane or dense top-k: candidates come from the
    must disjunction's worklist fold, and each filter/exclusion becomes a
    presence bitmap (one bool scatter over its own postings) gathered at
    the candidate docs. The dense path's lax.top_k over [N] — the
    dominant cost at shard scale — disappears; this is the config-3
    conjunction shape (BooleanQuery with required + filter clauses,
    ContextIndexSearcher.java:170-206)."""
    must_s, filter_s, must_not_s = spec[1], spec[3], spec[4]
    children = arrays["children"]
    live = seg["live"]
    num_docs = live.shape[0]
    with jax.named_scope(SCOPE_SCORE):
        (
            docs_s,
            run_sum,
            eligible,
            p,
            kk,
        ) = _sparse_candidates(seg, must_s[0], children[0], k, bounds=bounds)
        sentinel = jnp.int32(num_docs)
        safe_docs = jnp.minimum(docs_s, sentinel - 1)

        for idx_child, child_spec in enumerate(filter_s):
            eligible &= _const_membership(
                seg, child_spec, children[1 + idx_child], safe_docs, num_docs
            )
        base = 1 + len(filter_s)
        for idx_child, child_spec in enumerate(must_not_s):
            eligible &= ~_const_membership(
                seg, child_spec, children[base + idx_child], safe_docs,
                num_docs,
            )
        scores = run_sum * arrays["boost"]
        key = jnp.where(eligible, scores, jnp.float32(NEG_INF))
    return _sparse_top_k(key, docs_s, eligible, kk, min(kk, p))


def _sparse_lead_inner(seg, spec, arrays, k: int, bounds=None):
    """Lead-driven conjunction: candidates come from the MOST SELECTIVE
    clause — a single-span constant filter whose df undercuts the must
    disjunction's (spec[6], chosen statically at compile time from clause
    selectivities, the ConjunctionDISI lead-iterator cost ordering).

    The filter's posting span IS the candidate list, already sorted by
    doc id (CSR term→doc order) — no union worklist, NO SORT. Each must
    term then verifies + scores at the candidates with one binary search
    over its posting span plus one impact gather; contributions fold in
    term order, reproducing the oracle's per-term accumulation rounding
    exactly. Remaining filters/exclusions verify via _const_membership.
    Totals stay exact (every candidate is checked, none dropped)."""
    must_s, filter_s, must_not_s = spec[1], spec[3], spec[4]
    lead = _bool_lead(spec)
    children = arrays["children"]
    live = seg["live"]
    num_docs = live.shape[0]
    with jax.named_scope(SCOPE_SCORE):
        sentinel = jnp.int32(num_docs)
        lead_spec = filter_s[lead]
        docs, _vals, valid, _norm = _gather_tiles(
            lead_spec, children[1 + lead], seg
        )
        # [P], doc-ascending
        cand = jnp.where(valid, docs, sentinel).reshape(-1)
        p = cand.shape[0]
        safe = jnp.minimum(cand, sentinel - 1)
        in_range = cand != sentinel
        must_spec = must_s[0]
        marr = children[0]
        t_pad = must_spec[3]
        field_planes = seg["fields"][must_spec[1]]
        flat_docs = field_planes[0].reshape(-1)
        flat_tn = field_planes[1].reshape(-1)
        one = jnp.float32(1.0)
        score = jnp.zeros(p, dtype=jnp.float32)
        matched_any = jnp.zeros(p, dtype=bool)
        for j in range(t_pad):
            pos, found = _span_locate(
                flat_docs, marr["term_starts"][j], marr["term_ends"][j], safe
            )
            found &= in_range
            w = marr["term_weights"][j]
            contrib = w - w / (one + flat_tn[pos])
            score = score + jnp.where(found, contrib, jnp.float32(0.0))
            matched_any |= found
        eligible = matched_any & in_range & live[safe]
        if bounds is not None:
            eligible &= (cand >= bounds[0]) & (cand < bounds[1])
        for idx_child, child_spec in enumerate(filter_s):
            if idx_child == lead:
                continue
            eligible &= _const_membership(
                seg, child_spec, children[1 + idx_child], safe, num_docs
            )
        base = 1 + len(filter_s)
        for idx_child, child_spec in enumerate(must_not_s):
            eligible &= ~_const_membership(
                seg, child_spec, children[base + idx_child], safe, num_docs
            )
        scores = score * arrays["boost"]
        key = jnp.where(eligible, scores, jnp.float32(NEG_INF))
    # Candidate order ascends by doc id (one span, CSR order).
    kk = min(k, num_docs)
    return _sparse_top_k(key, cand, eligible, kk, min(kk, p))


def _span_locate(flat, start, end, cands):
    """(pos, found) for each candidate doc against the sorted slice
    [start, end) of a flat postings plane: pos = first in-span slot whose
    doc >= the candidate (clipped in-plane), found = that slot holds
    exactly the candidate. log2(plane) static binary-search steps, all
    vectorized gathers — the scatter-free conjunction primitive."""
    p = cands.shape[0]
    lo = jnp.broadcast_to(jnp.asarray(start, dtype=jnp.int32), (p,))
    hi = jnp.broadcast_to(jnp.asarray(end, dtype=jnp.int32), (p,))
    limit = jnp.int32(flat.shape[0] - 1)
    for _ in range(max(1, int(flat.shape[0]).bit_length())):
        mid = (lo + hi) >> 1
        v = flat[jnp.clip(mid, 0, limit)]
        go = v < cands
        lo = jnp.where(go, mid + 1, lo)
        hi = jnp.where(go, hi, mid)
    pos = jnp.clip(lo, 0, limit)
    return pos, (lo < end) & (flat[pos] == cands)


def _span_member(seg, field_name, start, end, cands):
    """bool[P]: is each candidate doc inside the sorted posting span
    [start, end) of the field's flat postings plane?"""
    flat = seg["fields"][field_name][0].reshape(-1)
    _pos, found = _span_locate(flat, start, end, cands)
    return found


def _sparse_candidates(seg, spec, arrays, k: int, bounds=None):
    """Shared candidate fold: (sorted candidate docs, left-fold run sums,
    run-head eligibility, P, clamped k) for a terms spec. `bounds` is the
    packed-plane tenant doc range [lo, hi): candidates outside it (which
    the worklist cannot produce unless a host plan bug pointed at another
    tenant's tiles) are masked ineligible."""
    live = seg["live"]
    num_docs = live.shape[0]
    t_pad = spec[3]
    docs, tn, valid, _norm = _gather_tiles(spec, arrays, seg, want="tn")
    w = arrays["weights"][:, None]
    contrib = w - w / (jnp.float32(1.0) + tn)
    sentinel = jnp.int32(num_docs)
    docs = jnp.where(valid, docs, sentinel).reshape(-1)
    contrib = jnp.where(valid, contrib, jnp.float32(0.0)).reshape(-1)
    p = docs.shape[0]
    docs_s, contrib_s = jax.lax.sort(
        (docs, contrib), num_keys=1, is_stable=True
    )
    docs_ext = jnp.concatenate(
        [docs_s, jnp.full(t_pad, num_docs + 1, dtype=docs_s.dtype)]
    )
    contrib_ext = jnp.concatenate(
        [contrib_s, jnp.zeros(t_pad, dtype=contrib_s.dtype)]
    )
    run_sum = contrib_s
    for j in range(1, t_pad):
        same = docs_ext[j : j + p] == docs_s
        run_sum = run_sum + jnp.where(
            same, contrib_ext[j : j + p], jnp.float32(0.0)
        )
    is_start = jnp.concatenate(
        [jnp.ones(1, dtype=bool), docs_s[1:] != docs_s[:-1]]
    )
    in_range = docs_s != sentinel
    live_at = live[jnp.minimum(docs_s, sentinel - 1)]
    eligible = is_start & in_range & live_at
    if bounds is not None:
        eligible &= (docs_s >= bounds[0]) & (docs_s < bounds[1])
    return docs_s, run_sum, eligible, p, min(k, num_docs)


def _sparse_terms_inner(seg, spec, arrays, k: int, bounds=None):
    """Candidate-centric top-k for a ("terms", field, NT, TP) spec.

    Left-fold run sums via static shifts (see _sparse_candidates): run
    length <= total query-term occurrences, bounded by the spec's T_pad
    bucket; top-k positions ascend by doc id, so lax.top_k's lowest-index
    tie-break IS Lucene's doc-id tie-break."""
    with jax.named_scope(SCOPE_SCORE):
        docs_s, run_sum, eligible, p, kk = _sparse_candidates(
            seg, spec, arrays, k, bounds=bounds
        )
        key = jnp.where(eligible, run_sum, jnp.float32(NEG_INF))
    return _sparse_top_k(key, docs_s, eligible, kk, min(kk, p))


def _sparse_top_k(key, cand, eligible, kk: int, kp: int):
    """(top scores, top doc ids, total) over candidate `key` scores of the
    docs `cand`: top-k of kp slots padded out to kk, and the eligible
    count. Candidate order ascends by doc id, so lax.top_k's lowest-index
    tie-break IS Lucene's doc-id tie-break."""
    with jax.named_scope(SCOPE_TOPK):
        top_scores, top_pos = jax.lax.top_k(key, kp)
        top_ids = cand[top_pos]
        # staticcheck: ignore[traced-branch] kp and kk are Python ints derived from the static spec's worklist shape, not traced values; the branch is resolved at trace time
        if kp < kk:  # more hits requested than candidate slots: pad
            top_scores = jnp.pad(
                top_scores, (0, kk - kp), constant_values=NEG_INF
            )
            top_ids = jnp.pad(top_ids, (0, kk - kp), constant_values=0)
    with jax.named_scope(SCOPE_TOTAL):
        total = jnp.sum(eligible, dtype=jnp.int32)
    return top_scores, top_ids.astype(jnp.int32), total


@partial(jax.jit, static_argnames=("spec", "k"))
def execute_sparse(seg, spec, arrays, k: int):
    """Candidate-centric execution of a pure terms spec (see block comment)."""
    return _sparse_inner(seg, spec, arrays, k)


@partial(jax.jit, static_argnames=("spec", "k"))
def execute_batch_sparse(seg, spec, arrays_batched, k: int):
    """Batched candidate-centric execution ([Q, ...] plan arrays)."""
    return jax.vmap(lambda arrays: _sparse_inner(seg, spec, arrays, k))(
        arrays_batched
    )


@partial(jax.jit, static_argnames=("spec", "k"))
def execute_sequential_sparse(seg, spec, arrays_batched, k: int):
    """Run Q same-spec queries STRICTLY one after another (latency bench).

    `execute_batch_sparse` vmaps Q queries into one fused program — the
    right serving mode, but its per-query time is batch-amortized and so
    cannot honestly answer "what is the p50 latency of a single _search?"
    (the BASELINE north-star metric). This kernel scans over the Q queries
    instead: `lax.scan` lowers to a sequential XLA while-loop, and each
    iteration's plan additionally depends on the previous iteration's
    result (the carried total-hits count feeds a `* 0.0` perturbation of
    the weights behind an `optimization_barrier`, which XLA cannot fold —
    `x * 0 → 0` is not a valid fp rewrite for a possibly-non-finite x).
    Iterations therefore cannot overlap or batch; wall time / Q is the
    true unbatched per-query device latency a PCIe-attached host observes.
    The carry is the (always finite) hit count, so the perturbation is
    exactly +0.0 and results stay bit-identical to the per-query kernel.
    """

    def step(carry, arrays):
        eps = jax.lax.optimization_barrier(carry) * jnp.float32(0.0)
        s, i, t = _sparse_inner(seg, spec, _chain_perturb(arrays, eps), k)
        return t.astype(jnp.float32), (s, i, t)

    _, out = jax.lax.scan(step, jnp.float32(0.0), arrays_batched)
    return out


def _chain_perturb(arrays, eps):
    """Dependency-chain a query plan on a prior result (see
    execute_sequential_sparse): adds an exactly-+0.0 perturbation derived
    from the carried value to the plan's top-level f32 leaf, so XLA cannot
    overlap or batch consecutive scan iterations. Plans with no f32 leaf
    (match_none compiles to empty arrays) pass through unperturbed — there
    is no device work to overlap for them anyway."""
    for key in ("boost", "weights"):
        if key in arrays:
            arrays = dict(arrays)
            arrays[key] = arrays[key] + eps
            break
    return arrays


def _inner_for(spec):
    return _sparse_inner if supports_sparse(spec) else _execute_inner


@partial(jax.jit, static_argnames=("spec", "k", "length"))
def execute_sequential(seg, spec, arrays_batched, k: int, length=None):
    """Strictly-sequential unbatched execution for ANY compiled spec.

    The dense-path counterpart of execute_sequential_sparse — the honest
    per-query latency kernel for bool/script/function_score plans (the
    BASELINE config-3/4/5 shapes). Results are bit-identical to the
    per-query kernel. `length` is only needed for specs whose plans carry
    no per-query arrays at all (match_none compiles to an empty pytree,
    leaving the scan length uninferrable)."""

    def step(carry, arrays):
        eps = jax.lax.optimization_barrier(carry) * jnp.float32(0.0)
        s, i, t = _inner_for(spec)(seg, spec, _chain_perturb(arrays, eps), k)
        return t.astype(jnp.float32), (s, i, t)

    _, out = jax.lax.scan(
        step, jnp.float32(0.0), arrays_batched, length=length
    )
    return out


# ---------------------------------------------------------------------------
# Multi-shard execution on ONE device: the scatter/gather phase when shard
# count exceeds device count (every shard's tree is stacked on a leading
# axis and vmapped — one program scores all shards, then an in-program
# merge takes the global top-k). The single-chip complement of
# parallel/sharded.py's shard_map path (same stacked layout, same merge
# contract: score desc, shard asc, doc asc — SearchPhaseController.java:398
# as one top_k over concatenated per-shard rank lists).
# ---------------------------------------------------------------------------


def _shards_inner(seg_stacked, spec, arrays_stacked, k: int, docs_per_shard: int):
    inner = _inner_for(spec)
    s, i, t = jax.vmap(lambda seg, arr: inner(seg, spec, arr, k))(
        seg_stacked, arrays_stacked
    )
    n_shards = s.shape[0]
    gids = i.astype(jnp.int32) + (
        jnp.arange(n_shards, dtype=jnp.int32) * jnp.int32(docs_per_shard)
    )[:, None]
    flat_s = s.reshape(-1)
    # Flattened index order is (shard, rank); per-shard ranks tie-break by
    # doc id ascending, so lax.top_k's lowest-index tie-break reproduces the
    # coordinator merge order exactly.
    top_s, pos = jax.lax.top_k(flat_s, min(k, flat_s.shape[0]))
    return top_s, gids.reshape(-1)[pos], jnp.sum(t, dtype=jnp.int32)


@partial(jax.jit, static_argnames=("spec", "k", "docs_per_shard"))
def execute_shards(seg_stacked, spec, arrays_stacked, k: int, docs_per_shard: int):
    """One query over S stacked shards on one device -> global top-k."""
    return _shards_inner(seg_stacked, spec, arrays_stacked, k, docs_per_shard)


@partial(jax.jit, static_argnames=("spec", "k", "docs_per_shard"))
def execute_shards_batch(
    seg_stacked, spec, arrays_batched, k: int, docs_per_shard: int
):
    """Q same-spec queries over S stacked shards ([Q, S, ...] plans)."""
    return jax.vmap(
        lambda arr: _shards_inner(seg_stacked, spec, arr, k, docs_per_shard)
    )(arrays_batched)


@partial(jax.jit, static_argnames=("spec", "k", "docs_per_shard"))
def execute_shards_sequential(
    seg_stacked, spec, arrays_batched, k: int, docs_per_shard: int
):
    """Strictly-sequential multi-shard execution (per-query p50 bench)."""

    def step(carry, arrays):
        eps = jax.lax.optimization_barrier(carry) * jnp.float32(0.0)
        s, i, t = _shards_inner(
            seg_stacked, spec, _chain_perturb(arrays, eps), k, docs_per_shard
        )
        return t.astype(jnp.float32), (s, i, t)

    _, out = jax.lax.scan(step, jnp.float32(0.0), arrays_batched)
    return out


# ---------------------------------------------------------------------------
# Fused two-phase rescore: query top-window, re-score the window with a
# second compiled plan, combine, global top-k — one launch, nothing leaves
# the device but the final k hits. The reference runs this as two separate
# phases (QueryPhase then RescorePhase, search/rescore/QueryRescorer.java);
# on TPU both phases fuse into one XLA program so the window never round-
# trips through the host.
# ---------------------------------------------------------------------------


def _rescore_inner(seg, spec, arrays, rspec, rarrays, k: int, window: int,
                   query_weight, rescore_weight):
    s, ids, total = _inner_for(spec)(seg, spec, arrays, window)
    live = seg["live"]
    num_docs = live.shape[0]
    rscores, rmatched = _eval_node(rspec, rarrays, seg, num_docs)
    relig = rmatched & live
    rs = jnp.where(relig, rscores, jnp.float32(0.0))[ids]
    rm = relig[ids]
    valid = s > jnp.float32(NEG_INF)
    qw = jnp.float32(query_weight)
    rw = jnp.float32(rescore_weight)
    comb = jnp.where(rm, qw * s + rw * rs, qw * s)
    comb = jnp.where(valid, comb, jnp.float32(NEG_INF))
    top_s, pos = jax.lax.top_k(comb, min(k, comb.shape[0]))
    return top_s, ids[pos], total


@partial(jax.jit, static_argnames=("spec", "rspec", "k", "window"))
def execute_rescore(seg, spec, arrays, rspec, rarrays, k: int, window: int,
                    query_weight, rescore_weight):
    """score_mode=total rescore: qw*orig + rw*rescore for window docs the
    rescore query matches, qw*orig otherwise; ties keep original rank."""
    return _rescore_inner(seg, spec, arrays, rspec, rarrays, k, window,
                          query_weight, rescore_weight)


@partial(jax.jit, static_argnames=("spec", "rspec", "k", "window"))
def execute_rescore_sequential(seg, spec, arrays_batched, rspec,
                               rarrays_batched, k: int, window: int,
                               query_weight, rescore_weight):
    """Strictly-sequential fused rescore (per-query p50 bench)."""

    def step(carry, pair):
        arrays, rarrays = pair
        eps = jax.lax.optimization_barrier(carry) * jnp.float32(0.0)
        s, i, t = _rescore_inner(
            seg, spec, _chain_perturb(arrays, eps), rarrays=rarrays,
            rspec=rspec, k=k, window=window, query_weight=query_weight,
            rescore_weight=rescore_weight,
        )
        return t.astype(jnp.float32), (s, i, t)

    _, out = jax.lax.scan(
        step, jnp.float32(0.0), (arrays_batched, rarrays_batched)
    )
    return out


@partial(jax.jit, static_argnames=("spec", "k"))
def execute(seg, spec, arrays, k: int):
    """Run a compiled query plan over one device segment.

    seg: {"fields": {name: (doc_ids i32[NT,S], tfs f32[NT,S],
                            norm_bytes u8[N+1], present bool[N])},
          "doc_values": {name: f32[N]}, "live": bool[N]}

    Returns (top_scores f32[k], top_ids i32[k], total_hits i32[]).
    Slots past total hits carry score -inf (host trims them).
    """
    return _execute_inner(seg, spec, arrays, k)


@partial(jax.jit, static_argnames=("spec", "k"))
def execute_batch(seg, spec, arrays_batched, k: int):
    """Run a batch of same-spec compiled queries in one program.

    The msearch-style serving mode: arrays_batched leaves carry a leading
    query axis [Q, ...]; one dispatch + one device→host transfer serves the
    whole batch (amortizing host/device round-trip latency, the dominant
    cost for small per-query work). Returns ([Q, k] scores, [Q, k] ids,
    [Q] totals).
    """
    return jax.vmap(lambda arrays: _execute_inner(seg, spec, arrays, k))(
        arrays_batched
    )


@partial(jax.jit, static_argnames=("spec", "k"))
def execute_score_asc(seg, spec, arrays, k: int):
    """Bottom-k by score (explicit {"_score": "asc"} sorts).

    Ineligible docs mask to +inf so they can never enter the bottom-k; ties
    break by ascending doc id like the descending path.
    """
    live = seg["live"]
    num_docs = live.shape[0]
    scores, matched = _eval_node(spec, arrays, seg, num_docs)
    eligible = matched & live
    masked = jnp.where(eligible, scores, jnp.float32(jnp.inf))
    kk = min(k, num_docs)
    neg_top, top_ids = jax.lax.top_k(-masked, kk)
    total = jnp.sum(eligible, dtype=jnp.int32)
    return -neg_top, top_ids.astype(jnp.int32), total


def execute_auto(seg, spec, arrays, k: int):
    """Single-query execution via the best kernel for the spec."""
    if supports_sparse(spec):
        return execute_sparse(seg, spec, arrays, k)
    return execute(seg, spec, arrays, k)


# ---------------------------------------------------------------------------
# MaxScore disjunction (Turtle & Flood, 1995; Lucene scores disjunctions
# the same way once the collector's threshold lets it skip, TopDocs-
# CollectorContext.java:68). The plan (query/compile._maxscore_arrays)
# splits the terms into essential ones, whose posting tiles form the
# worklist, and non-essential ones N, which no doc matching only them can
# score past `ub_n`. Candidates are the essential postings' docs; each is
# scored exactly — essential contributions from its sorted run, N's by a
# search of each N span — and folded in query-term order, the
# accumulation order of the exhaustive kernel and the oracle. When the
# k-th candidate score θ beats `ub_n` (with the fp32 margin
# execute_batch_blockmax uses), no skipped doc can enter the top k, and
# ids and scores are exact. N's postings, nearly all of a stop-word
# query's, are never read.
#
# spec: ("terms_maxscore", field, NT, T_pad, R), R a static bound on the
#       tile rows of any span of the segment (see _locate_in_spans)
# arrays: {"tile_ids", "starts", "ends", "term_of": i32[NT],
#          "weights": f32[NT],          — the essential worklist
#          "n_term", "n_starts", "n_ends": i32[N_pad],
#          "n_weights": f32[N_pad],     — N's spans (n_term T_pad = pad)
#          "ub_n": f32[], "total_floor": i32[] — the largest term span}
# Returns (top scores, top ids, total, ok): total = max(candidates,
# total_floor), a lower bound on the matches that exceeds the request's
# track_total_hits threshold; `ok` false means θ did not beat ub_n and
# the answer must come from the exhaustive plan instead.
# ---------------------------------------------------------------------------


def supports_maxscore(spec) -> bool:
    """Is this a MaxScore plan (query/compile.Compiler.compile_maxscore)?"""
    return isinstance(spec, tuple) and bool(spec) and spec[0] == "terms_maxscore"


def _locate_in_spans(doc_tiles, starts, ends, cands, fence_rows: int):
    """(found, row, col), each [S, P]: where each candidate doc sits in
    each of S sorted posting spans [starts[s], ends[s]) of a tiled plane.

    On the TPU a gather costs about the same per index whether it reads
    one value or a whole tile row, so a binary search of ~20 scalar
    gathers per candidate is the slow way. Instead the first doc of each
    of the span's tile rows (at most `fence_rows`, a static bound) is
    sliced out once as a fence, every candidate counts the fence entries
    at or below it (no gather) to find its row, and one row gather per
    candidate and span settles it."""
    n_rows = doc_tiles.shape[0]
    fence_rows = min(fence_rows, n_rows)
    iota = jnp.arange(TILE, dtype=jnp.int32)[None, :]
    found, rows, cols = [], [], []
    for s in range(starts.shape[0]):
        start, end = starts[s], ends[s]
        t0, t1 = start // TILE, (end - 1) // TILE
        row0 = jnp.clip(t0, 0, n_rows - fence_rows)
        first = jax.lax.dynamic_slice(
            doc_tiles, (row0, jnp.int32(0)), (fence_rows, 1)
        )[:, 0]
        row_ids = row0 + jnp.arange(fence_rows, dtype=jnp.int32)
        # The span's first row may open with another term's postings.
        fence = jnp.where(row_ids == t0, jnp.int32(-1), first)
        fence = jnp.where(
            (row_ids >= t0) & (row_ids <= t1), fence, jnp.iinfo(jnp.int32).max
        )
        below = jnp.sum(
            fence[None, :] <= cands[:, None], axis=1, dtype=jnp.int32
        )
        row = jnp.clip(t0 + below - 1, 0, n_rows - 1)
        pos = row[:, None] * TILE + iota
        hit = (
            (doc_tiles[row] == cands[:, None]) & (pos >= start) & (pos < end)
        )
        found.append(jnp.any(hit, axis=1))
        rows.append(row)
        cols.append(jnp.argmax(hit, axis=1).astype(jnp.int32))
    return jnp.stack(found), jnp.stack(rows), jnp.stack(cols)


def _maxscore_inner(seg, spec, arrays, k: int):
    _, field_name, _nt, t_pad, fence_rows = spec
    n_pad = arrays["n_term"].shape[0]
    live = seg["live"]
    num_docs = live.shape[0]
    one = jnp.float32(1.0)
    zero = jnp.float32(0.0)
    with jax.named_scope(SCOPE_SCORE):
        docs, tn, valid, _norm = _gather_tiles(spec, arrays, seg)
        w = arrays["weights"][:, None]
        sentinel = jnp.int32(num_docs)
        docs = jnp.where(valid, docs, sentinel).reshape(-1)
        contrib = jnp.where(valid, w - w / (one + tn), zero).reshape(-1)
        term = jnp.where(
            valid, arrays["term_of"][:, None], jnp.int32(t_pad)
        ).reshape(-1)
        p = docs.shape[0]
        docs_s, term_s, contrib_s = jax.lax.sort(
            (docs, term, contrib), num_keys=1
        )
        # per[i, j]: term j's contribution to the doc of position i, read
        # from the doc's run (at most one posting per term occurrence, so
        # each sum has one non-zero addend at most and is exact).
        terms_iota = jnp.arange(t_pad, dtype=jnp.int32)[None, :]
        docs_ext = jnp.concatenate(
            [docs_s, jnp.full(t_pad, num_docs + 1, dtype=docs_s.dtype)]
        )
        term_ext = jnp.concatenate(
            [term_s, jnp.full(t_pad, t_pad, dtype=term_s.dtype)]
        )
        contrib_ext = jnp.concatenate(
            [contrib_s, jnp.zeros(t_pad, dtype=contrib_s.dtype)]
        )
        per = jnp.zeros((p, t_pad), dtype=jnp.float32)
        for r in range(t_pad):
            hit = (docs_ext[r : r + p] == docs_s)[:, None] & (
                term_ext[r : r + p][:, None] == terms_iota
            )
            per = per + jnp.where(hit, contrib_ext[r : r + p][:, None], zero)
        safe = jnp.minimum(docs_s, sentinel - 1)
        doc_tiles, tn_tiles = seg["fields"][field_name][:2]
        found, rows, cols = _locate_in_spans(
            doc_tiles, arrays["n_starts"], arrays["n_ends"], safe, fence_rows
        )
        nw = arrays["n_weights"][:, None]
        n_tn = tn_tiles[rows, cols]
        n_contrib = jnp.where(found, nw - nw / (one + n_tn), zero)
        for s in range(n_pad):
            per = per + jnp.where(
                arrays["n_term"][s] == terms_iota, n_contrib[s][:, None], zero
            )
        score = jnp.zeros(p, dtype=jnp.float32)
        for j in range(t_pad):  # left fold in query-term order
            score = score + per[:, j]
        is_start = jnp.concatenate(
            [jnp.ones(1, dtype=bool), docs_s[1:] != docs_s[:-1]]
        )
        eligible = is_start & (docs_s != sentinel) & live[safe]
        key = jnp.where(eligible, score, jnp.float32(NEG_INF))
    kk = min(k, num_docs)
    top_scores, top_ids, n_cand = _sparse_top_k(
        key, docs_s, eligible, kk, min(kk, p)
    )
    with jax.named_scope(SCOPE_TOTAL):
        total = jnp.maximum(n_cand, arrays["total_floor"])
        margin = arrays["ub_n"] * jnp.float32(1 + 1e-6) + jnp.float32(1e-6)
        ok = top_scores[kk - 1] > margin
    return top_scores, top_ids, total, ok


@partial(jax.jit, static_argnames=("spec", "k"))
def execute_maxscore(seg, spec, arrays, k: int):
    """One MaxScore disjunction (see the block comment above)."""
    return _maxscore_inner(seg, spec, arrays, k)


@partial(jax.jit, static_argnames=("spec", "k"))
def execute_batch_maxscore(seg, spec, arrays_batched, k: int):
    """Batched MaxScore disjunctions ([Q, ...] plan arrays)."""
    return jax.vmap(lambda arrays: _maxscore_inner(seg, spec, arrays, k))(
        arrays_batched
    )


# ---------------------------------------------------------------------------
# After-cursor execution (search_after / scroll continuation).
#
# The cursor is (after_key, after_doc): a doc qualifies when its key is
# strictly past the cursor, or ties the cursor key with a LARGER local doc
# id — the (key, doc id) total order the merge contract uses. A key-only
# cursor (REST search_after with no _doc tiebreak) passes after_doc =
# num_docs so the equality clause never fires. Totals stay the FULL match
# count: ES reports hits.total independent of the cursor.
# ---------------------------------------------------------------------------


@partial(jax.jit, static_argnames=("spec", "k", "ascending"))
def execute_score_after(seg, spec, arrays, k: int, after_score, after_doc,
                        ascending: bool = False):
    """Score-ordered top-k strictly after the (score, doc) cursor."""
    live = seg["live"]
    num_docs = live.shape[0]
    scores, matched = _eval_node(spec, arrays, seg, num_docs)
    eligible = matched & live
    iota = jnp.arange(num_docs, dtype=jnp.int32)
    if ascending:
        past = scores > after_score
    else:
        past = scores < after_score
    keep = eligible & (past | ((scores == after_score) & (iota > after_doc)))
    if ascending:
        masked = jnp.where(keep, scores, jnp.float32(jnp.inf))
        neg_top, top_ids = jax.lax.top_k(-masked, min(k, num_docs))
        top_scores = -neg_top
    else:
        masked = jnp.where(keep, scores, jnp.float32(NEG_INF))
        top_scores, top_ids = jax.lax.top_k(masked, min(k, num_docs))
    total = jnp.sum(eligible, dtype=jnp.int32)
    n_after = jnp.sum(keep, dtype=jnp.int32)
    return top_scores, top_ids.astype(jnp.int32), total, n_after


@partial(
    jax.jit, static_argnames=("spec", "field_name", "desc", "k", "missing_first")
)
def execute_sorted_after(seg, spec, arrays, field_name: str, desc: bool,
                         k: int, after_key, after_doc,
                         missing_first: bool = False):
    """Field-sorted top-k strictly after the (key, doc) cursor.

    `after_key` lives in the transformed ascending key space (negated for
    desc, missing = +/-f32 max per the missing directive) so one
    comparison covers both directions and the missing region."""
    live = seg["live"]
    num_docs = live.shape[0]
    _, matched = _eval_node(spec, arrays, seg, num_docs)
    eligible = matched & live
    col, key = sort_key_plane(seg, field_name, desc, missing_first)
    iota = jnp.arange(num_docs, dtype=jnp.int32)
    keep = eligible & (
        (key > after_key) | ((key == after_key) & (iota > after_doc))
    )
    masked = jnp.where(keep, key, jnp.float32(jnp.inf))
    _neg, ids = jax.lax.top_k(-masked, min(k, num_docs))
    values = col[ids]
    total = jnp.sum(eligible, dtype=jnp.int32)
    n_after = jnp.sum(keep, dtype=jnp.int32)
    return values, ids.astype(jnp.int32), total, n_after


def execute_many(seg, compiled_queries, k: int):
    """Grouped msearch: batch same-spec queries, one launch per shape group.

    Queries keep their natural pow-2 worklist buckets (no padding to the
    global max), so total device work tracks actual postings touched; the
    per-launch round-trip is amortized within each group. Term-disjunction
    groups run on the candidate-centric kernel. Returns results in input
    order: a list of (scores f32[k], ids i32[k], total int).
    """
    from collections import defaultdict

    groups = defaultdict(list)
    for pos, c in enumerate(compiled_queries):
        groups[c.spec].append(pos)
    results: list = [None] * len(compiled_queries)
    for spec, positions in groups.items():
        # Stack on the HOST: one device transfer per leaf per group. A
        # jnp.stack here would upload every query's small arrays one by one
        # — measured 3000x slower through the host<->TPU link.
        arrays_b = jax.tree.map(
            lambda *xs: np.stack(xs),
            *[compiled_queries[p].arrays for p in positions],
        )
        kernel = execute_batch_sparse if supports_sparse(spec) else execute_batch
        scores_b, ids_b, totals_b = jax.device_get(
            kernel(seg, spec, arrays_b, k)
        )
        for row, p in enumerate(positions):
            results[p] = (scores_b[row], ids_b[row], int(totals_b[row]))
    return results


def execute_batch_blockmax(seg, spec, arrays_list, k: int, instruments=None):
    """Two-launch thresholded batch execution — the block-max WAND analog.

    Lucene skips non-competitive posting blocks against the collector's
    running k-th score (block-max WAND, enabled by search/query/
    TopDocsCollectorContext.java:68). Data-dependent pointer skipping is
    XLA-hostile, so the TPU form is *tile filtering* (SURVEY §7):

      launch 1: sparse-score each query's A highest-upper-bound worklist
                entries; θ[q] = k-th best partial run sum — partial sums
                are lower bounds on full scores, so θ lower-bounds the
                final k-th score;
      host:     drop every entry whose tile upper bound plus the other
                terms' global upper bounds can't reach θ (with an fp32
                safety margin), then re-bucket the survivors — typically a
                much smaller pow-2 worklist;
      launch 2: sparse-score the surviving entries exactly.

    Soundness: a pruned tile only contains docs whose full score is < θ ≤
    final k-th score, so no top-k doc loses a contribution — top-k ids and
    scores are exact. Total hits become lower bounds (docs matched only by
    pruned tiles go uncounted) — precisely Lucene's `"relation": "gte"`
    totals under WAND skipping.

    Returns (scores [Q,k'], ids [Q,k'], totals [Q], relation) with
    relation "gte" when any pruning occurred, else "eq".
    """
    nt = spec[2]
    kind, field_name, _, t_pad = spec
    a_bucket = max(8, nt // 4)
    stacked = {
        name: np.stack([a[name] for a in arrays_list])
        for name in ("tile_ids", "starts", "ends", "weights", "ub", "ub_other")
    }
    if a_bucket >= nt:  # tiny worklists: single launch, exact totals
        s, i, t = jax.device_get(
            execute_batch_sparse(seg, spec, stacked, k)
        )
        return s, i, t, "eq"

    # Launch 1: each query's top-UB subset, selected with ONE batched
    # argsort + take_along_axis — no per-query python loops. (Reordering
    # is safe here — phase-A scores are only lower bounds; exact
    # accumulation order matters only in the final launch.)
    spec_a = (kind, field_name, a_bucket, t_pad)
    order = np.argsort(-stacked["ub"], axis=1, kind="stable")[:, :a_bucket]
    arrays_a = {
        name: np.take_along_axis(stacked[name], order, axis=1)
        for name in stacked
    }
    scores_a, _, _ = jax.device_get(
        execute_batch_sparse(seg, spec_a, arrays_a, k)
    )
    q = len(arrays_list)
    thetas = (
        scores_a[:, k - 1]
        if scores_a.shape[1] >= k
        else np.full(q, -np.inf, dtype=np.float32)
    )

    # Host prune + re-bucket, fully vectorized. keep preserves original
    # worklist order (the exact left-fold in launch 2 needs it): a stable
    # argsort on ~keep moves survivors to the front without reordering
    # them.
    margin = thetas.astype(np.float32) * np.float32(1 - 1e-6) - np.float32(
        1e-6
    )
    keep = (stacked["ub"] + stacked["ub_other"]) >= margin[:, None]
    keep |= ~np.isfinite(thetas)[:, None]  # underfull top-k: keep all
    counts = keep.sum(axis=1)
    pruned_any = bool((counts < nt).any())
    if instruments is not None:
        # Prune effectiveness, per query (obs/metrics.py
        # blockmax_pruned_tile_fraction histogram).
        for c in counts:
            instruments.blockmax_pruned(1.0 - float(c) / nt)
    nt_b = 1 << (max(1, int(counts.max())) - 1).bit_length()
    front = np.argsort(~keep, axis=1, kind="stable")[:, :nt_b]
    arrays_b = {
        name: np.take_along_axis(stacked[name], front, axis=1)
        for name in stacked
    }
    # Rows past each query's survivor count are padding: an empty span
    # never validates, and the pad tile keeps gathers in-range.
    pad = np.arange(nt_b)[None, :] >= counts[:, None]
    arrays_b["starts"] = np.where(pad, 0, arrays_b["starts"])
    arrays_b["ends"] = np.where(pad, 0, arrays_b["ends"])
    spec_b = (kind, field_name, nt_b, t_pad)
    s, i, t = jax.device_get(execute_batch_sparse(seg, spec_b, arrays_b, k))
    return s, i, t, ("gte" if pruned_any else "eq")


# ---------------------------------------------------------------------------
# Two-phase block-max CONJUNCTION execution — the BMW analog for the
# sparse bool shape (required terms + constant filters). Same structure as
# execute_batch_blockmax, but phase A runs the full conjunction over each
# query's A highest-upper-bound MUST tiles (filters verified at
# candidates), so θ = the k-th best filter-passing partial score — a
# lower bound on the final k-th score. The host then drops must tiles
# whose upper bound plus the other terms' bounds cannot reach θ and
# re-buckets the survivors for the exact second launch. Top-k ids/scores
# are exact; totals become "gte" when any tile was pruned (docs matched
# only by pruned tiles go uncounted), so serving gates this backend
# behind untracked totals exactly like the disjunction block-max.
# ---------------------------------------------------------------------------

# The must child's worklist-entry planes that phase subsets reorder.
_CONJ_ENTRY_KEYS = ("tile_ids", "starts", "ends", "weights", "ub", "ub_other")


def supports_blockmax_conj(spec) -> bool:
    """Two-phase pruned execution applies to the must-driven sparse
    conjunction shape: a scored terms must (whose worklist carries
    block-max upper bounds) with constant filters/exclusions and the
    default lead (-1; a filter-led fold has no sort worth pruning)."""
    return (
        isinstance(spec, tuple)
        and bool(spec)
        and spec[0] == "bool"
        and supports_sparse(spec)
        and _bool_lead(spec) == -1
        and bool(spec[1])
        and spec[1][0][0] == "terms"
    )


def _with_must_nt(spec, nt: int):
    """The bool spec with its (single) must child re-bucketed to nt."""
    must_spec = spec[1][0]
    new_must = (must_spec[0], must_spec[1], nt, must_spec[3])
    # staticcheck: ignore[bool-spec] star-tail rebuild copies every other field verbatim, so arity is preserved by construction (ops/ stays import-free of query/compile)
    return ("bool", (new_must,), *spec[2:])


def _subset_must_child(child: dict, order: np.ndarray) -> dict:
    """Reorder/subset the must child's worklist planes along the tile
    axis (the trailing axis of `order`); per-term planes pass through."""
    out = dict(child)
    for name in _CONJ_ENTRY_KEYS:
        if name in out:
            out[name] = np.take_along_axis(out[name], order, axis=-1)
    return out


def execute_batch_blockmax_conj(seg, spec, arrays_list, k: int,
                                instruments=None):
    """Two-launch thresholded conjunction batch over one segment.

    Returns (scores [Q,k'], ids [Q,k'], totals [Q], relation) with
    relation "gte" when any pruning occurred, else "eq".
    """
    must_spec = spec[1][0]
    nt = must_spec[2]
    stacked = jax.tree.map(lambda *xs: np.stack(xs), *arrays_list)
    a_bucket = max(8, nt // 4)
    if a_bucket >= nt:  # tiny worklists: single launch, exact totals
        s, i, t = jax.device_get(execute_batch_sparse(seg, spec, stacked, k))
        return s, i, t, "eq"
    child0 = stacked["children"][0]
    ub, ub_other = child0["ub"], child0["ub_other"]  # [Q, nt]
    q = ub.shape[0]

    # Launch 1: the conjunction over each query's top-UB must subset.
    # (Reordering is safe — phase-A scores are only lower bounds; exact
    # accumulation order matters only in the final launch.)
    order = np.argsort(-ub, axis=-1, kind="stable")[..., :a_bucket]
    arrays_a = {
        **stacked,
        "children": (
            _subset_must_child(child0, order),
            *stacked["children"][1:],
        ),
    }
    scores_a, _, _ = jax.device_get(
        execute_batch_sparse(seg, _with_must_nt(spec, a_bucket), arrays_a, k)
    )
    thetas = (
        scores_a[..., k - 1]
        if scores_a.shape[-1] >= k
        else np.full(q, -np.inf, dtype=np.float32)
    )

    # Host prune + re-bucket (same fp32 safety margin as the disjunction
    # path); keep preserves worklist order via the stable ~keep argsort.
    # θ lives in the bool's boosted score space while ub/ub_other carry
    # only term weights, so the bounds scale by the per-query boost
    # before comparing (a non-positive boost disables pruning — every
    # bound degenerates).
    boost = np.asarray(stacked["boost"], dtype=np.float32).reshape(q)
    margin = thetas.astype(np.float32) * np.float32(1 - 1e-6) - np.float32(
        1e-6
    )
    keep = (ub + ub_other) * boost[:, None] >= margin[:, None]
    keep |= ~np.isfinite(thetas)[:, None]  # underfull top-k: keep all
    keep |= (boost <= 0)[:, None]
    counts = keep.sum(axis=-1)
    pruned_any = bool((counts < nt).any())
    if instruments is not None:
        for c in counts:
            instruments.blockmax_pruned(1.0 - float(c) / nt)
    nt_b = 1 << (max(1, int(counts.max())) - 1).bit_length()
    front = np.argsort(~keep, axis=-1, kind="stable")[..., :nt_b]
    child_b = _subset_must_child(child0, front)
    pad = np.arange(nt_b)[None, :] >= counts[..., None]
    child_b["starts"] = np.where(pad, 0, child_b["starts"])
    child_b["ends"] = np.where(pad, 0, child_b["ends"])
    arrays_b = {**stacked, "children": (child_b, *stacked["children"][1:])}
    s, i, t = jax.device_get(
        execute_batch_sparse(seg, _with_must_nt(spec, nt_b), arrays_b, k)
    )
    return s, i, t, ("gte" if pruned_any else "eq")


def execute_shards_blockmax_conj(seg_stacked, spec, arrays_list, k: int,
                                 docs_per_shard: int, instruments=None):
    """Two-launch thresholded conjunction batch over S stacked shards.

    arrays_list: per-query plan pytrees with [S, ...] leaves (the stacked
    compile). θ comes from each query's MERGED phase-A top-k, so one
    shard's strong candidates prune other shards' hopeless tiles too.
    Returns (scores [Q,k'], global ids [Q,k'], totals [Q], relation).
    """
    must_spec = spec[1][0]
    nt = must_spec[2]
    stacked = jax.tree.map(lambda *xs: np.stack(xs), *arrays_list)
    a_bucket = max(8, nt // 4)
    if a_bucket >= nt:
        s, i, t = jax.device_get(
            execute_shards_batch(seg_stacked, spec, stacked, k,
                                 docs_per_shard)
        )
        return s, i, t, "eq"
    child0 = stacked["children"][0]
    ub, ub_other = child0["ub"], child0["ub_other"]  # [Q, S, nt]
    q = ub.shape[0]
    order = np.argsort(-ub, axis=-1, kind="stable")[..., :a_bucket]
    arrays_a = {
        **stacked,
        "children": (
            _subset_must_child(child0, order),
            *stacked["children"][1:],
        ),
    }
    scores_a, _, _ = jax.device_get(
        execute_shards_batch(
            seg_stacked, _with_must_nt(spec, a_bucket), arrays_a, k,
            docs_per_shard,
        )
    )
    thetas = (
        scores_a[..., k - 1]
        if scores_a.shape[-1] >= k
        else np.full(q, -np.inf, dtype=np.float32)
    )
    # Bound/threshold spaces as in execute_batch_blockmax_conj: scale the
    # term-weight bounds by the bool boost (uniform across shards — the
    # same query compiles every shard) before comparing against θ.
    boost = np.asarray(stacked["boost"], dtype=np.float32).reshape(
        q, -1
    )[:, 0]
    margin = thetas.astype(np.float32) * np.float32(1 - 1e-6) - np.float32(
        1e-6
    )
    keep = (ub + ub_other) * boost[:, None, None] >= margin[:, None, None]
    keep |= ~np.isfinite(thetas)[:, None, None]
    keep |= (boost <= 0)[:, None, None]
    counts = keep.sum(axis=-1)  # [Q, S]
    pruned_any = bool((counts < nt).any())
    if instruments is not None:
        for row in counts:
            instruments.blockmax_pruned(1.0 - float(row.mean()) / nt)
    nt_b = 1 << (max(1, int(counts.max())) - 1).bit_length()
    front = np.argsort(~keep, axis=-1, kind="stable")[..., :nt_b]
    child_b = _subset_must_child(child0, front)
    pad = np.arange(nt_b)[None, None, :] >= counts[..., None]
    child_b["starts"] = np.where(pad, 0, child_b["starts"])
    child_b["ends"] = np.where(pad, 0, child_b["ends"])
    arrays_b = {**stacked, "children": (child_b, *stacked["children"][1:])}
    s, i, t = jax.device_get(
        execute_shards_batch(
            seg_stacked, _with_must_nt(spec, nt_b), arrays_b, k,
            docs_per_shard,
        )
    )
    return s, i, t, ("gte" if pruned_any else "eq")


# ---------------------------------------------------------------------------
# Packed multi-tenant execution: B (query, tenant) lanes over ONE shared
# plane (index/tiles.py PackedPlane). Each lane's plan arrays are already
# in packed coordinates (compiled through the plane's per-member views);
# the lane additionally carries its tenant's GLOBAL doc bounds [lo, hi).
# One vmapped launch scores every lane — the dispatch amortization that
# makes tiny indices competitive (BENCH_r05 cfg1: a 5k-doc corpus paid
# ~2 ms dispatch per query against ~0.17 ms of oracle work). Isolation is
# structural (a lane's worklist tiles lie in its own tenant's tile range)
# and enforced (eligibility is masked to [lo, hi) inside the kernel), and
# scores are bit-exact with per-tenant execution: the plan arrays are the
# same values shifted, so the fold order and fp32 rounding are identical.
# ---------------------------------------------------------------------------


_PACKED_KINDS = ("terms", "terms_gather", "terms_const", "match_none")


def supports_packed(spec) -> bool:
    """May this compiled spec execute on a packed multi-tenant plane?

    Packed planes concatenate only the inverted-field postings planes, so
    eligible specs are trees of term-worklist nodes (every match/term/
    terms query and bool combinations thereof — the small-tenant hot
    shapes). Anything touching doc values, positions, vectors or nested
    blocks stays on the per-tenant path."""
    if not isinstance(spec, tuple) or not spec:
        return False
    kind = spec[0]
    if kind in _PACKED_KINDS:
        return True
    if kind == "const":
        return supports_packed(spec[1])
    if kind == "bool":
        return all(supports_packed(c) for group in spec[1:5] for c in group)
    return False


@partial(jax.jit, static_argnames=("spec", "k"))
def execute_batch_packed(seg, spec, arrays_batched, lo_b, hi_b, k: int):
    """Score B same-spec lanes against one packed plane in one launch.

    arrays_batched: plan pytree with leading lane axis [B, ...], compiled
    in packed coordinates. lo_b/hi_b: i32[B] per-lane tenant doc bounds.
    Returns ([B, k'] scores, [B, k'] TENANT-LOCAL ids, [B] totals) —
    result-identical per lane to executing the lane's query on its
    tenant's own plane (slots past each lane's total are padding).
    """
    inner = _sparse_inner if supports_sparse(spec) else _execute_inner

    def one(arrays, lo, hi):
        s, ids, t = inner(seg, spec, arrays, k, bounds=(lo, hi))
        return s, ids - lo, t

    return jax.vmap(one)(arrays_batched, lo_b, hi_b)


def packed_segment_tree(plane) -> dict[str, Any]:
    """The jit-input pytree view of an index.tiles.PackedPlane (the
    packed counterpart of segment_tree; only inverted fields exist)."""
    return {
        "fields": {
            name: (pf.doc_ids, pf.tn, pf.tfs, pf.norm_bytes, pf.present)
            for name, pf in plane.fields.items()
        },
        "positions": {},
        "doc_values": {},
        "vectors": {},
        "live": plane.live,
        "nested": {},
    }


def sort_key_plane(seg, field_name: str, desc: bool, missing_first: bool):
    """Transformed ascending sort-key plane for a doc-values column:
    negate for desc, missing (NaN) pinned to +/-f32max per the missing
    directive (FieldSortBuilder missing-value semantics). Shared by the
    single-segment sort kernels and the SPMD mesh program so both paths
    rank by bit-identical keys."""
    col = seg["doc_values"][field_name]
    key = -col if desc else col
    fmax = jnp.float32(jnp.finfo(jnp.float32).max)
    miss = -fmax if missing_first else fmax
    return col, jnp.where(jnp.isnan(key), miss, key)


@partial(
    jax.jit, static_argnames=("spec", "field_name", "desc", "k", "missing_first")
)
def execute_sorted(seg, spec, arrays, field_name: str, desc: bool, k: int,
                   missing_first: bool = False):
    """Query + field sort: top-k by a doc-values column, missing first or
    last per the sort's missing directive (default last).

    Mirrors the reference's TopFieldCollector path with ES FieldSortBuilder
    semantics. Ties break by ascending doc id. Returns (values f32[k] raw
    field values (NaN = missing), ids i32[k], total_hits i32[]).
    """
    live = seg["live"]
    num_docs = live.shape[0]
    _, matched = _eval_node(spec, arrays, seg, num_docs)
    eligible = matched & live
    col, key = sort_key_plane(seg, field_name, desc, missing_first)
    key = jnp.where(eligible, key, jnp.float32(jnp.inf))  # ineligible last
    kk = min(k, num_docs)
    _neg_top, ids = jax.lax.top_k(-key, kk)
    values = col[ids]
    total = jnp.sum(eligible, dtype=jnp.int32)
    return values, ids.astype(jnp.int32), total


@partial(jax.jit, static_argnames=("spec",))
def compute_filter_mask(seg, spec, arrays):
    """Evaluate one filter-context plan to its matched plane — the
    device-resident bitset the filter cache stores (index/filter_cache).

    The live mask is deliberately NOT applied: deletions AND in at query
    time exactly as for recomputed filters, so cached planes survive
    soft-deletes unchanged (postings/doc-values are immutable per packed
    segment; only refresh/merge produce new segments — and new cache
    keys)."""
    num_docs = seg["live"].shape[0]
    _, matched = _eval_node(spec, arrays, seg, num_docs)
    return matched


@partial(jax.jit, static_argnames=("spec",))
def compute_filter_mask_row(seg_row, spec, arrays):
    """compute_filter_mask over one shard row of a mesh (every leaf
    [1, ...], as MeshView packs them): the [1, N] row of the stacked
    [S, N] plane, computed on the row's own device."""
    seg = jax.tree.map(lambda x: x[0], seg_row)
    return compute_filter_mask(seg, spec, arrays)[None]


@partial(jax.jit, static_argnames=("spec",))
def compute_filter_mask_stacked(seg_stacked, spec, arrays_stacked):
    """Per-shard filter-mask planes over S stacked shards ([S, N] bool)
    — the mesh-path (parallel/sharded.py) form of compute_filter_mask."""

    def one(seg, arrays):
        num_docs = seg["live"].shape[0]
        _, matched = _eval_node(spec, arrays, seg, num_docs)
        return matched

    return jax.vmap(one)(seg_stacked, arrays_stacked)


@partial(jax.jit, static_argnames=("spec",))
def execute_dense(seg, spec, arrays):
    """Dense (scores, matched) over all docs — for rescoring/aggregations."""
    live = seg["live"]
    num_docs = live.shape[0]
    scores, matched = _eval_node(spec, arrays, seg, num_docs)
    eligible = matched & live
    return jnp.where(eligible, scores, jnp.float32(0.0)), eligible


@partial(jax.jit, static_argnames=("spec",))
def scores_at(seg, spec, arrays, ids):
    """Evaluate a query and gather (scores, matched) at specific doc ids.

    The rescore-phase primitive (the reference's QueryRescorer re-scores
    only the top-window docs, action/search + search/rescore/RescorePhase):
    dense evaluation stays on device; only the window is gathered out.
    """
    live = seg["live"]
    num_docs = live.shape[0]
    scores, matched = _eval_node(spec, arrays, seg, num_docs)
    eligible = matched & live
    scores = jnp.where(eligible, scores, jnp.float32(0.0))
    return scores[ids], eligible[ids]


def segment_tree(device_segment) -> dict[str, Any]:
    """Build the jit-input pytree view of a DeviceSegment."""
    return {
        "fields": {
            name: (f.doc_ids, f.tn, f.tfs, f.norm_bytes, f.present)
            for name, f in device_segment.fields.items()
        },
        "positions": {
            name: (f.pos_doc, f.pos_val)
            for name, f in device_segment.fields.items()
            if f.pos_doc is not None
        },
        "doc_values": dict(device_segment.doc_values),
        "vectors": dict(device_segment.vectors),
        "live": device_segment.live,
        "nested": {
            path: {"tree": segment_tree(inner), "parent_of": parent_of}
            for path, (inner, parent_of) in device_segment.nested.items()
        },
    }
