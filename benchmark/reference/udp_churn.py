"""Plain reference for generator kind `udp_churn`: what a node must emit
while a rolling deploy renames a share of its timer and counter keys every
interval.  numpy only; imports nothing of the program (`udp_zipf.py`,
`tdigest_rule.py` and `tdigest_compress.py`, beside this file, are the
benchmark's own).

From the generator's model it knows, for every interval, each live name
(the slot's generation is in it) and the samples sent to it.  `plan` lists
the names the collector has to keep from each sink batch, over every
interval the run may reach (`plan_intervals`); per interval the compared
timer slots are the `sampled_hot_ranks` hottest ranks, the
`sampled_renamed` busiest slots RENAMED IN THAT INTERVAL, as many renamed
the interval before, and a seeded draw from the rest — and beside every
compared name stands the name the slot carried one generation earlier,
which must stay silent.  `compare` is `udp_zipf`'s, name by name (hazen
for at most `limits.hot_key_samples` samples, rank error within
`limits.hot_rank_widths` cluster widths for a compressed key, `.count`,
counters exact, gauges last write, sets within the HLL bound, every timer
line counted, three percentile metrics per touched name and no more), and
on top of it:

  * `renamed_keys_not_clean`: compared names in their FIRST interval that
    miss any of those limits — a recycled row that carried something over
    reads a wrong `.count`, `min`, `max` or percentile here;
  * `retired_names_emitted`: kept names that answered in an interval that
    sent them no line (an earlier generation's, or a slot no line touched);
  * `intervals_without_renamed_keys`: measured intervals whose sample held
    no touched name renamed in it, or none renamed the interval before;
  * `intervals_beyond_plan`: measured intervals the plan did not reach.
"""

from __future__ import annotations

import importlib.util
import os

import numpy as np


def _beside(name: str):
    spec = importlib.util.spec_from_file_location(
        f"bench_reference_{name}_base",
        os.path.join(os.path.dirname(os.path.abspath(__file__)),
                     f"{name}.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


zref = _beside("udp_zipf")
tc, rule, F32_STEP = zref.tc, zref.rule, zref.F32_STEP
SUFFIXES = ("min", "max", "count")


class Model:
    """The generator's model, read once: the variants' lines, each slot's
    block, and per (variant, slot) what its samples must answer."""

    def __init__(self, gen, seed: int, p: dict, cfg: dict):
        self.gen, self.seed, self.p, self.cfg = gen, seed, p, cfg
        self.base = {v: gen.zipf.model(seed, p, v)
                     for v in range(p["variants"])}
        self.block = {fam: gen.slot_block(seed, p, fam)
                      for fam in gen.FAMILIES}
        self._sorted = {}
        self._stats = {}
        self._rest = {}
        self._samples = {}

    def deploy(self, run_interval: int) -> int:
        return self.gen.deploy_interval(self.p, run_interval)

    def gens(self, fam: str, n: int) -> np.ndarray:
        return self.gen.generation(self.block[fam], self.p, n)

    def renamed(self, fam: str, n: int) -> np.ndarray:
        return self.gen.renamed(self.block[fam], self.p, n)

    def sample(self, n: int) -> dict:
        """Deploy interval n's compared timer slots, by why they are
        compared: `hot`, `renamed` (in n), `renamed_before` (in n - 1),
        `rest`."""
        p = self.p
        v = n % p["variants"]
        # the variant and the two blocks come round again
        turn = (v, n % p["churn_period"])
        if turn not in self._samples:
            self._samples[turn] = self._sample(n, v)
        return self._samples[turn]

    def _sample(self, n: int, v: int) -> dict:
        p = self.p
        m = self.base[v]
        n_all = min(p["sampled_keys"], p["timer_keys"])
        hot = m["rank_key"][:min(p["sampled_hot_ranks"], n_all)]
        out = {"hot": hot}
        taken = set(hot.tolist())
        for name, when in (("renamed", n), ("renamed_before", n - 1)):
            slots = self.renamed("t", when)
            # the busiest first: the touched ones, the hot among them
            slots = slots[np.argsort(-m["key_count"][slots],
                                     kind="stable")]
            slots = np.array([s for s in slots.tolist()
                              if s not in taken][:p["sampled_renamed"]],
                             np.int64)
            out[name] = slots
            taken.update(slots.tolist())
        if v not in self._rest:
            self._rest[v] = np.random.default_rng(
                [int(self.seed), 23, v]).permutation(p["timer_keys"])
        rest = [s for s in self._rest[v].tolist() if s not in taken]
        out["rest"] = np.array(rest[:max(0, n_all - len(taken))], np.int64)
        return out

    def stats(self, v: int, slot: int):
        """What variant v's samples of `slot` must answer; None where it
        received no line."""
        key = (v, slot)
        if key not in self._stats:
            self._stats[key] = self._compute(v, slot)
        return self._stats[key]

    def _compute(self, v: int, slot: int):
        m = self.base[v]
        if v not in self._sorted:
            order = np.argsort(m["t_key"], kind="stable")
            self._sorted[v] = (m["t_val"][order], np.concatenate(
                [[0], np.cumsum(m["key_count"])]))
        vals, starts = self._sorted[v]
        s = np.sort(vals[starts[slot]:starts[slot + 1]])
        if not len(s):
            return None
        pcts = self.cfg["server"]["percentiles"]
        e = {"sorted": s, "n": len(s), "min": s[0], "max": s[-1],
             "span": (s[-1] - s[0]) or 1.0}
        if len(s) <= self.cfg["limits"]["hot_key_samples"]:
            e["hazen"] = np.percentile(s, np.asarray(pcts) * 100.0,
                                       method="hazen")
        else:
            delta = float(
                self.cfg["guarantees_numbers"]["digest_compression"])
            e["curve"] = tc.rank_curve(s)
            e["one_stage"] = tc.one_stage_quantiles(
                s, pcts, delta, rule.weighted_quantiles)
            e["one_stage_rank"] = [tc.rank_of(e["curve"], x)
                                   for x in e["one_stage"]]
        return e


def _timer_names(gen, slot: int, g: int, pcts) -> list:
    base = gen.timer_name(slot, g)
    return ([f"{base}.{int(q * 100)}percentile" for q in pcts]
            + [f"{base}.{s}" for s in SUFFIXES])


def plan(gen, seed: int, p: dict, cfg: dict) -> dict:
    pcts = cfg["server"]["percentiles"]
    mdl = Model(gen, seed, p, cfg)
    wanted, named = set(), set()
    first, last = mdl.deploy(0), mdl.deploy(p["plan_intervals"] - 1)
    for n in range(first, last + 1):
        t_gen = mdl.gens("t", n)
        for slots in mdl.sample(n).values():
            for s in slots.tolist():
                # the live name, and the one it retired
                for g in {int(t_gen[s]), max(int(t_gen[s]) - 1, 0)}:
                    if (s, g) not in named:
                        named.add((s, g))
                        wanted.update(_timer_names(gen, s, g, pcts))
    g0, g1 = mdl.gens("c", first - 1), mdl.gens("c", last)
    for s in range(p["counter_keys"]):
        wanted.update(gen.counter_name(s, g)
                      for g in range(max(int(g0[s]), 0), int(g1[s]) + 1))
    wanted.update(f"{gen.PREFIX}.g.{k}" for k in range(p["gauge_keys"]))
    wanted.update(f"{gen.PREFIX}.s.{k}" for k in range(p["set_keys"]))
    return {"wanted": wanted, "model": mdl,
            "count_suffix": ".count", "count_prefix": f"{gen.PREFIX}.t."}


def compare(gen, seed: int, p: dict, cfg: dict, pl: dict,
            intervals: list[dict]) -> list[dict]:
    """`intervals`: one dict per measured interval: `interval` (its number
    in the run; the deploy's is `aged_intervals` later), `got` (name ->
    value for the wanted names), `count_sum`, `percentile_metrics`.
    Returns the numbers compared, each beside its limit."""
    pcts = cfg["server"]["percentiles"]
    lim = float(cfg["limits"]["percentile_span_err"])
    widths = float(cfg["limits"]["hot_rank_widths"])
    delta = float(cfg["guarantees_numbers"]["digest_compression"])
    rank_lim = {q: widths * tc.cluster_width(q, delta) for q in pcts}
    precision = int(cfg["server"].get("set_precision", 14))
    hll_rel = max(3.0 * 1.04 / np.sqrt(2.0 ** precision), 0.03)
    mdl = pl["model"]
    pre = gen.PREFIX
    shallow = {q: 0.0 for q in pcts}
    hot = {q: 0.0 for q in pcts}
    hot_vs_one = {q: 0.0 for q in pcts}
    worst_minmax = worst_set = 0.0
    hot_keys = shallow_keys = 0
    missing = counts_wrong = counters_wrong = gauges_wrong = 0
    samples_lost = pm_missing = 0
    not_clean = retired = no_renamed = beyond = 0
    for iv in intervals:
        if iv["interval"] >= p["plan_intervals"]:
            beyond += 1
            continue
        n = mdl.deploy(iv["interval"])
        v = n % p["variants"]
        m, got = mdl.base[v], iv["got"]
        t_gen, c_gen = mdl.gens("t", n), mdl.gens("c", n)
        seen = {"renamed": 0, "renamed_before": 0}
        for why, slots in mdl.sample(n).items():
            for k in slots.tolist():
                e = mdl.stats(v, k)
                if e is None:
                    continue    # not touched: its names must be silent
                base = gen.timer_name(k, int(t_gen[k]))
                bad = False
                for suffix in ("min", "max"):
                    have = got.get(f"{base}.{suffix}")
                    if have is None:
                        missing += 1
                        bad = True
                    else:
                        err = abs(have - e[suffix]) / e["span"]
                        worst_minmax = max(worst_minmax, err)
                        bad |= err > lim
                wrong = got.get(f"{base}.count") != e["n"]
                counts_wrong += wrong
                bad |= wrong
                is_hot = "one_stage" in e
                hot_keys += is_hot
                shallow_keys += not is_hot
                for j, q in enumerate(pcts):
                    have = got.get(f"{base}.{int(q * 100)}percentile")
                    if have is None:
                        missing += 1
                        bad = True
                    elif is_hot:
                        rank = tc.rank_of(e["curve"], have)
                        hot[q] = max(hot[q], abs(rank - q))
                        hot_vs_one[q] = max(hot_vs_one[q], abs(
                            rank - e["one_stage_rank"][j]))
                        bad |= abs(rank - q) > rank_lim[q]
                    else:
                        off = (abs(have - e["hazen"][j])
                               - F32_STEP * abs(e["hazen"][j])) / e["span"]
                        shallow[q] = max(shallow[q], off)
                        bad |= off > lim
                if why in seen:
                    seen[why] += 1
                    not_clean += bad and why == "renamed"
        no_renamed += not (seen["renamed"] and seen["renamed_before"])
        c_want = np.bincount(m["c_key"], weights=m["c_val"],
                             minlength=p["counter_keys"])
        for k in np.nonzero(c_want)[0].tolist():
            counters_wrong += got.get(
                gen.counter_name(k, int(c_gen[k]))) != c_want[k]
        # every kept timer or counter name that answered: its slot must
        # carry that generation now and have been sent a line
        for name in got:
            part = name.split(".")
            if part[1] == "t":
                gens, sent = t_gen, m["key_count"]
            elif part[1] == "c":
                gens, sent = c_gen, c_want
            else:
                continue
            k = int(part[2])
            retired += not (int(part[3][1:]) == gens[k] and sent[k] > 0)
        g_last = dict(zip(m["g_key"].tolist(), m["g_val"].tolist()))
        gauges_wrong += sum(
            1 for k, val in g_last.items()
            if not abs(got.get(f"{pre}.g.{k}", np.nan) - val) <= 1e-3)
        for k in np.unique(m["s_key"]).tolist():
            true = len(np.unique(m["s_mem"][m["s_key"] == k]))
            have = got.get(f"{pre}.s.{k}", np.nan)
            r = abs(have - true) / max(5.0, hll_rel * true)
            worst_set = max(worst_set, float("inf") if np.isnan(r) else r)
        samples_lost += abs(p["timer_lines"] - int(round(iv["count_sum"])))
        pm_missing += abs(int((m["key_count"] > 0).sum()) * len(pcts)
                          - iv["percentile_metrics"])
    out = [{"name": f"p{int(q * 100)}_span_err_vs_hazen",
            "value": shallow[q], "limit": lim} for q in pcts]
    out += [{"name": f"hot_p{int(q * 100)}_rank_err",
             "value": hot[q], "limit": rank_lim[q]} for q in pcts]
    out += [{"name": f"hot_p{int(q * 100)}_rank_dist_vs_one_stage",
             "value": hot_vs_one[q], "limit": 2.0 * rank_lim[q]}
            for q in pcts]
    out += [
        {"name": "minmax_span_err", "value": worst_minmax, "limit": lim},
        {"name": "sampled_metrics_missing", "value": missing, "limit": 0},
        {"name": "sampled_counts_not_exact", "value": int(counts_wrong),
         "limit": 0},
        # the comparison must have had both kinds of key to look at
        {"name": "intervals_without_hot_keys",
         "value": int(hot_keys == 0) + int(shallow_keys == 0), "limit": 0},
        {"name": "counters_not_exact", "value": int(counters_wrong),
         "limit": 0},
        {"name": "gauges_not_last_write", "value": gauges_wrong, "limit": 0},
        {"name": "set_err_over_hll_bound", "value": worst_set, "limit": 1.0},
        {"name": "timer_samples_not_counted", "value": samples_lost,
         "limit": 0},
        {"name": "percentile_metrics_missing", "value": pm_missing,
         "limit": 0},
        {"name": "renamed_keys_not_clean", "value": int(not_clean),
         "limit": 0},
        {"name": "retired_names_emitted", "value": int(retired), "limit": 0},
        {"name": "intervals_without_renamed_keys", "value": int(no_renamed),
         "limit": 0},
        {"name": "intervals_beyond_plan", "value": int(beyond), "limit": 0},
    ]
    return out
