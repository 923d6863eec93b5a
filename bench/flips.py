"""Where the widest logit gaps of a DeepSeek-V3.2 (MLA) cell come from.

    python bench/flips.py --workload <cell> --seconds <s> --seeds 1 2 ...
                          [--top 8] [--typical 64]

Runs the cell once per seed as bench/run.py does (weights, traffic and the
engine drawn from the seed, a window of `--seconds`), keeping after every
tick each layer's Top-K selection of each token served (the engine's
`prev_topk`). Then the float32 reference (bench/reference/mla.py) runs
over each served session once, keeping its residual stream, and reads
each served token's gap. The `--top` tokens of widest gap are recomputed
(`row_logits`) with:

    topk    the reference's Top-K selection in every layer set to the
            program's (`overlap`: the positions the two share, per layer)
    group   in one expert layer, a group that holds a held expert turned
            over across the boundary of the `topk_group` kept ones
    expert  in one expert layer, the held expert nearest the boundary of
            the `top_k` chosen turned over across it

each routing flip alone and on the program's selection, and each with its
margin, the difference of the two routing scores on either side. A token
served because the program's selection or a routing near-tie went
otherwise reads, under that change, a gap near 0 (`explained`); a fault
in the program leaves the gap. The smallest routing margins of
`--typical` other served tokens, drawn from the seed, give the margins a
token meets by chance (`route_share`: the share of them as near). One
JSON line per seed.
"""

from __future__ import annotations

import argparse
import gc
import json
import sys
import time
from pathlib import Path
from typing import Dict, List

import numpy as np

ROOT = Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

# a gap that rounding reads where no decision went otherwise: the widest
# `chatglm3-6b.decode5k`'s program reads over 20 seeds is 0.0709
NEAR = 0.1


def _ranked(choice: np.ndarray, keep, groups: int) -> np.ndarray:
    """The experts of the groups `keep` by choice score, best first (ties
    to the lowest index, as `jax.lax.top_k`'s go)."""
    inside = np.repeat(np.isin(np.arange(groups), sorted(keep)),
                       choice.size // groups)
    return np.argsort(-np.where(inside, choice, -np.inf), kind="stable")


def _noaux_tc(sig: np.ndarray, bias: np.ndarray, moe: dict):
    """(choice scores, group scores, groups best first, kept groups)."""
    choice = sig + bias
    score = np.sort(choice.reshape(moe["n_group"], -1), axis=1)[:, -2:].sum(1)
    order_g = np.argsort(-score, kind="stable")
    return choice, score, order_g, set(order_g[:moe["topk_group"]].tolist())


def chosen(sig: np.ndarray, bias: np.ndarray, moe: dict) -> List[int]:
    """noaux_tc's top_k experts for one token."""
    choice, _, _, kept = _noaux_tc(sig, bias, moe)
    return _ranked(choice, kept, moe["n_group"])[:moe["top_k"]].tolist()


def route_flips(sig: np.ndarray, bias: np.ndarray, moe: dict) -> List[dict]:
    """noaux_tc's decisions for one token that concern a held expert,
    each with its margin and the `top_k` experts chosen were it turned
    over: one `group` entry per group holding a held expert, and one
    `expert` entry for the held expert nearest the boundary of the chosen
    (where its group is kept)."""
    groups, top_g, k = moe["n_group"], moe["topk_group"], moe["top_k"]
    size = sig.size // groups
    first = moe.get("first_expert", 0)
    held = np.arange(first, first + moe["num_experts"])
    choice, score, order_g, kept = _noaux_tc(sig, bias, moe)
    out = []
    for g in sorted(set((held // size).tolist())):
        if g in kept:
            other = int(order_g[top_g])
            margin, keep = score[g] - score[other], kept - {g} | {other}
        else:
            other = int(order_g[top_g - 1])
            margin, keep = score[other] - score[g], kept - {other} | {g}
        out.append({"kind": "group", "margin": float(margin),
                    "route": _ranked(choice, keep, groups)[:k].tolist()})
    order = _ranked(choice, kept, groups)
    now = order[:k].tolist()
    near = None
    for e in held.tolist():
        if e // size not in kept:
            continue
        if e in now:
            other = int(order[k])
            margin = choice[e] - choice[other]
            route = [other if c == e else c for c in now]
        else:
            other = int(order[k - 1])
            margin = choice[other] - choice[e]
            route = [e if c == other else c for c in now]
        if near is None or margin < near["margin"]:
            near = {"kind": "expert", "margin": float(margin), "route": route}
    return out + ([near] if near else [])


def decisions(spec: dict, flat, sigs) -> List[dict]:
    """`route_flips` of every expert layer, from a row's router scores
    (`row_logits`'s second result, from layer 0)."""
    nd = spec["first_k_dense"]
    out = []
    for layer, sig in enumerate(sigs):
        if sig.size:
            bias = np.asarray(flat["moe_router_bias"][layer - nd], np.float32)
            out += [dict(d, layer=layer) for d in
                    route_flips(sig, bias, spec["moe"])]
    return out


def record_topk(eng, reqs: Dict[int, object]) -> Dict[int, Dict]:
    """Wrap `eng.tick` so that each tick keeps, for every request it served
    a token, its slot's Top-K positions per layer (L, K): `log[uid][g]`
    for the g-th token served. Returns `log`."""
    log: Dict[int, Dict[int, np.ndarray]] = {}
    tick = eng.tick

    def tick_and_keep():
        slots = {u: (r.slot, len(r.generated)) for u, r in reqs.items()
                 if r.slot is not None}
        out = tick()
        if slots and "prev_topk" in eng.state:
            topk = np.asarray(eng.state["prev_topk"])
            for u, (slot, n) in slots.items():
                if len(reqs[u].generated) > n:
                    log.setdefault(u, {})[n] = topk[:, slot]
        return out
    eng.tick = tick_and_keep
    return log


def _explain(ref, spec, flat, xs, p, tok, ds, theirs, sparse) -> List[dict]:
    """Row p's gap under each change: the program's selection (`topk`),
    each routing flip alone, and each on the program's selection."""
    picks = {} if theirs is None or not sparse else dict(enumerate(theirs))
    tries = [dict(d, on_program_topk=False) for d in ds]
    if picks:
        tries = ([{"kind": "topk", "layer": 0, "margin": None,
                   "on_program_topk": True}] + tries
                 + [dict(d, on_program_topk=True) for d in ds])
    for d in tries:
        forced = picks if d["on_program_topk"] else {}
        route = d.pop("route", None)
        flipped, _, _ = ref.row_logits(
            spec, flat, xs, p, sparse=sparse,
            start=0 if forced else d["layer"], topk=forced,
            route={d["layer"]: route} if route is not None else None)
        d["gap"] = float(flipped.max() - flipped[tok])
    return tries


def flips(reg, spec: dict, mix: dict, *, seed: int, seconds: float,
          top: int, typical: int) -> Dict:
    """One seed's reading of a cell's configuration and mix (see the
    module docstring)."""
    import jax
    import jax.numpy as jnp
    from bench import correct, flops, program, serve, weights

    specs = reg.generator(mix["generator"]).generate(
        mix, seed, vocab=spec["vocab"], seconds=seconds)
    flat = weights.make_flat(spec, seed, reg)
    eng = program.build_engine(spec, mix["engine"],
                               weights.program_params(flat, spec, reg))
    driver = serve.Driver(eng, specs)
    topk_log = record_topk(eng, driver.reqs)
    st = driver.run(seconds=seconds, warm_s=mix.get("warm_s", 0.0))
    reqs = driver.reqs
    jax.tree.map(lambda a: a.delete(), eng.state)
    driver.eng = eng = None
    gc.collect()

    closed = all(s["due_s"] is None for s in specs)
    uids = correct.choose(reqs, st, closed, mix, seed)
    seqs, rows, served = correct.teacher_forced(reqs, uids)
    ref = reg.reference(spec["reference"])
    sparse = flops.sparse_cell(spec, mix["engine"]["max_len"])
    s = -(-max(len(q) for q in seqs) // 512) * 512
    t = time.perf_counter()
    streams, gaps, off = [], [], 0
    with jax.default_matmul_precision("highest"):
        for q, r in zip(seqs, rows):
            xs = [np.asarray(x) for x in ref.stream(spec, flat, q, s,
                                                    sparse=sparse)]
            streams.append(xs)
            lg = ref.head(spec, flat, jnp.asarray(xs[-1][r]))
            tok = served[off:off + len(r)]
            off += len(r)
            gaps.append(lg.max(-1) - lg[np.arange(len(r)), tok])
    # each served token: (session, position, its index among the served)
    where = [(j, int(p), g) for j, r in enumerate(rows)
             for g, p in enumerate(r)]
    gap = np.concatenate(gaps)
    ranked = np.argsort(-gap, kind="stable")
    widest = ranked[:top].tolist()
    others = np.random.default_rng(seed).choice(
        ranked[top:], size=min(typical, len(ranked) - top),
        replace=False).tolist()
    stream_s = time.perf_counter() - t

    tokens, margins = [], []
    with jax.default_matmul_precision("highest"):
        for j in range(len(seqs)):
            mine = [n for n in widest + others if where[n][0] == j]
            if not mine:
                continue
            xs = [jnp.asarray(x) for x in streams[j]]
            for n in mine:
                _, p, g = where[n]
                tok = int(served[n])
                lg, sigs, own = ref.row_logits(spec, flat, xs, p,
                                               sparse=sparse)
                ds = decisions(spec, flat, sigs)
                near = min((d["margin"] for d in ds), default=float("inf"))
                if n in others:
                    margins.append(near)
                    continue
                theirs = topk_log.get(uids[j], {}).get(g)
                tries = _explain(ref, spec, flat, xs, p, tok, ds, theirs,
                                 sparse)
                best = min(tries, key=lambda d: d["gap"], default=None)
                tokens.append({
                    "uid": int(uids[j]), "position": p, "gap": float(gap[n]),
                    "gap_row": float(lg.max() - lg[tok]), "served": tok,
                    "best": int(np.argmax(lg)),
                    "overlap": None if theirs is None or not sparse else [
                        int(np.intersect1d(a, b).size)
                        for a, b in zip(own, theirs)],
                    "route_margin": near, "flip": best,
                    "explained": bool(best is not None
                                      and best["gap"] <= NEAR),
                    "tries": tries})
            del xs
    tokens.sort(key=lambda d: -d["gap"])
    typical_m = np.array(margins)
    for d in tokens:
        # the share of typical tokens with a routing decision as near
        d["route_share"] = float(np.mean(typical_m <= d["route_margin"]))
    finite = typical_m[np.isfinite(typical_m)]
    return {"seed": seed, "served": int(len(gap)),
            "logit_gap": float(gap.max()),
            "logit_gap_mean": float(gap.mean()),
            "logit_gap_top8": float(np.mean(gap[ranked[:8]])),
            "explained": sum(d["explained"] for d in tokens),
            "tokens": tokens, "typical": len(margins),
            # the smallest, the 5th, 25th and 50th percentiles
            "typical_route_margin": [
                float(v) for v in np.quantile(finite, [0, 0.05, 0.25, 0.5])]
            if finite.size else [],
            "stream_s": stream_s, "seconds": time.perf_counter() - t}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--top", type=int, default=8)
    ap.add_argument("--typical", type=int, default=64)
    args = ap.parse_args(argv)
    import jax
    if jax.devices()[0].platform != "tpu":
        print("flips: no TPU found", file=sys.stderr)
        return 3
    from bench import run as run_mod
    from bench.registry import Registry
    run_mod.enable_cache()
    reg = Registry()
    cell = reg.workload(args.workload)
    spec, mix = reg.config(cell["config"]), reg.traffic(cell["traffic"])
    for seed in args.seeds:
        print(json.dumps(flips(reg, spec, mix, seed=seed,
                               seconds=args.seconds, top=args.top,
                               typical=args.typical)), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
