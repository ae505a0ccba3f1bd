"""Kernels: the k-NN inference programs' share of their roofline.

Work: every ``impute_flush`` span inside the profiled window, at its
``computed`` cells against its attribute's reference rows and features
(``work.knn_work``).  Time: the device seconds of the masked-distance and
top-k programs in the same window.  The least time is the larger of the
work's operations over peak FLOP/s and its bytes over peak bytes/s, per
flush; the share is that least time over the device time."""

from work import knn_work, peaks, roofline_s

PROGRAMS = ("masked_distance", "top_k")


def read(ctx):
    trace = ctx["trace"]
    if not trace:
        return None
    device_s = sum(s for name, s in trace["modules_s"].items()
                   if any(p in name for p in PROGRAMS))
    if device_s <= 0:
        return None
    peak = peaks(ctx["device_kind"])
    least = {"compute": 0.0, "memory": 0.0}
    for name, _t0, _t1, args in ctx["traced_spans"]:
        if name != "impute_flush" or not args.get("computed"):
            continue
        nr, d = ctx["knn_shapes"][args["attr"]]
        secs, bound = roofline_s(*knn_work(args["computed"], nr, d,
                                           min(ctx["k"], nr)), peak)
        least[bound] += secs
    total = least["compute"] + least["memory"]
    if total <= 0:
        return None
    ctx["log"](f"knn_roofline: least {total:.6g} s (compute-bound "
               f"{least['compute']:.6g} s, memory-bound "
               f"{least['memory']:.6g} s) over {device_s:.6g} device s")
    return 100.0 * total / device_s
