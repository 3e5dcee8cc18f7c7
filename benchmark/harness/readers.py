"""The reader kinds of the per-layer metrics. A metric is a file
benchmark/layer_metrics/<name>.json: {"reader": <kind>, "params": {...}}.
Every reader takes (params, sources) and returns a number, or None when it
finds nothing to read (the harness then leaves the metric out of the line).

sources:
  spans     the program's JSONL span records emitted inside the window
  counters  Counters.delta over the window
  recorder  wrap.CallRecorder, with t0/t1 of the window
  profile   profile.reduce_trace result of the traced stretch (or None)
  units     how many timed calls / replay windows the traced stretch held,
            and how many operations the window attempted:
            {"profile": n, "attempted": m}
  gc        env.GcWatch: every collection with its generation and pause
  series    the driver's own named lists of numbers taken inside the window
            (Driver.series(); a driver without the method gives none)
"""

from __future__ import annotations

import statistics

from .profile import reduce_trace
from .stats import percentile


def _stat(values, stat: str):
    if not values:
        return None
    if stat == "median":
        return statistics.median(values)
    if stat == "mean":
        return statistics.fmean(values)
    if stat == "sum":
        return float(sum(values))
    if stat == "count":
        return float(len(values))
    if stat.startswith("p"):
        return percentile(values, float(stat[1:]))
    raise ValueError(f"unknown statistic {stat!r}")


def _match(rec: dict, where: dict | None) -> bool:
    return all(rec.get(k) in v for k, v in (where or {}).items())


def span_stat(params: dict, src: dict):
    """Statistic of one field of the program's spans of one name; with
    `over`, the ratio of the statistic under `where` to the statistic under
    `over` (a share of lanes, say)."""
    recs = [r for r in src["spans"] if r.get("name") == params["span"]
            and params["field"] in r]
    num = _stat([float(r[params["field"]]) for r in recs
                 if _match(r, params.get("where"))], params["stat"])
    if "over" in params:
        den = _stat([float(r[params["field"]]) for r in recs
                     if _match(r, params["over"])], params["stat"])
        if not den:
            return None
        return (num or 0.0) / den * params.get("scale", 1.0)
    return None if num is None else num * params.get("scale", 1.0)


def _counter_sum(delta: dict, terms: list) -> float:
    total = 0.0
    for term in terms:
        labels = tuple(term["labels"])
        for key, v in delta.get(term["counter"], {}).items():
            if tuple(key[:len(labels)]) == labels:
                total += v
    return total


def counter_ratio(params: dict, src: dict):
    """Sum of some counters' movement over the sum of others'."""
    den = _counter_sum(src["counters"], params["den"])
    if den <= 0:
        return None
    return (_counter_sum(src["counters"], params["num"]) / den
            * params.get("scale", 1.0))


def wrapped_call_stat(params: dict, src: dict):
    """Statistic of the harness's timers around named callables, over the
    calls that started inside the window; `self` subtracts the wrapped calls
    made inside (list them under `children` so that they get wrapped)."""
    rec = src["recorder"]
    if rec is None:
        return None
    durs = rec.durations(params["targets"], src["t0"], src["t1"],
                         self_time=params.get("self", False))
    if not durs:
        return None
    return _stat(durs, params["stat"]) * params.get("scale", 1.0)


def device_busy(params: dict, src: dict):
    """Seconds in which an operation ran on the device during the traced
    stretch, per timed call or replay window of the stretch; `module` keeps
    the operations of one program."""
    trace = src.get("trace")
    n = src["units"]["profile"]
    if trace is None or not n:
        return None
    red = (reduce_trace(trace, module=params["module"])
           if params.get("module") else src["profile"])
    if red["busy_s"] <= 0:
        return None
    return red["busy_s"] / n * params.get("scale", 1.0)


def gc_stat(params: dict, src: dict):
    """The interpreter's collections of one generation that started inside
    the window. `stat` is over their pauses in seconds (`count`, `sum`,
    `mean`...); `per` divides by `attempted` (operations of the window) or
    `window_s` (its length). A window without such a collection reads 0 for
    a count or a sum, and nothing for any other statistic."""
    watch = src.get("gc")
    if watch is None:
        return None
    pauses = watch.between(params["generation"], src["t0"], src["t1"])
    value = _stat(pauses, params["stat"])
    if value is None:
        if params["stat"] not in ("count", "sum"):
            return None
        value = 0.0
    per = params.get("per")
    if per:
        den = (src["t1"] - src["t0"] if per == "window_s"
               else src["units"].get(per))
        if not den:
            return None
        value /= den
    return value * params.get("scale", 1.0)


def driver_series(params: dict, src: dict):
    """Statistic of one of the driver's own series."""
    value = _stat((src.get("series") or {}).get(params["series"], []),
                  params["stat"])
    return None if value is None else value * params.get("scale", 1.0)


KINDS = {"span_stat": span_stat, "counter_ratio": counter_ratio,
         "wrapped_call_stat": wrapped_call_stat, "device_busy": device_busy,
         "gc_stat": gc_stat, "driver_series": driver_series}


def wrap_targets(specs) -> list[str]:
    """Every callable the cell's wrapped_call_stat metrics want timed."""
    out = []
    for spec in specs:
        if spec["reader"] == "wrapped_call_stat":
            for t in spec["params"]["targets"] + spec["params"].get("children", []):
                if t not in out:
                    out.append(t)
    return out


def read_all(metrics, src: dict) -> dict:
    out = {}
    for entry, spec in metrics:
        kind = KINDS.get(spec["reader"])
        if kind is None:
            raise ValueError(f"{entry['name']}: unknown reader {spec['reader']!r}")
        value = kind(spec["params"], src)
        if value is not None:
            out[entry["name"]] = {"value": value, "unit": entry["unit"]}
    return out
