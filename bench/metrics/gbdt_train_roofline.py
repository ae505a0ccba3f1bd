"""Kernels: the boosting programs' share of their roofline.

Work: every ``gbdt:fit`` span inside the profiled window, at its training
``rows`` (not the padded bucket) and its table's feature count
(``work_gbdt.train_work``: the least an exact implementation must do).
Time: the device seconds of the boosting programs (XLA modules named for
``boost``) in the same window.  The least time is the larger of the
work's operations over peak FLOP/s and its bytes over peak bytes/s, per
fit; the share is that least time over the device time.  Nothing where
the window holds no boosting program or no fit."""

from work import peaks, roofline_s
from work_gbdt import train_work

PROGRAM = "boost"


def read(ctx):
    trace = ctx["trace"]
    gbdt = ctx.get("gbdt")
    if not trace or not gbdt:
        return None
    device_s = sum(s for name, s in trace["modules_s"].items()
                   if PROGRAM in name)
    if device_s <= 0:
        return None
    peak = peaks(ctx["device_kind"])
    least = {"compute": 0.0, "memory": 0.0}
    for name, _t0, _t1, args in ctx["traced_spans"]:
        if name != "gbdt:fit" or not args.get("rows"):
            continue
        secs, bound = roofline_s(*train_work(
            args["rows"], ctx["gbdt_features"][args["table"]],
            gbdt["rounds"], gbdt["depth"], gbdt["max_bin"]), peak)
        least[bound] += secs
    total = least["compute"] + least["memory"]
    if total <= 0:
        return None
    ctx["log"](f"gbdt_train_roofline: least {total:.6g} s (compute-bound "
               f"{least['compute']:.6g} s, memory-bound "
               f"{least['memory']:.6g} s) over {device_s:.6g} device s")
    return 100.0 * total / device_s
