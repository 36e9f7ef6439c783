"""ops kernels: for every Convolution node the trace names (the
executor's named_scope), the least time its forward, data-gradient and
weight-gradient kernels could take on this chip (larger of FLOPs over
peak and bytes over bandwidth, from shapes, harness/costs.py) over the
device time the trace gives that node. Elementwise work that XLA fused
into a convolution counts against it. Leaves which bound in
facts["notes"]."""
from perfbench.harness import costs


def read(facts):
    red = facts["trace"]
    steps = red.step_count(fallback=facts.get("window_steps"))
    if not steps:
        return None
    per_chip = facts["batch"] // facts["chips"]
    nodes = costs.resnet_nodes(facts["config"], per_chip)
    seen = red.labels()
    ideal, measured, bound = 0.0, 0.0, {"compute": 0, "memory": 0}
    for name, cost in nodes.items():
        if name == "fc1" or name not in seen:
            continue
        least, by = costs.roofline_seconds(cost["kernels"], facts["peaks"])
        ideal += least * steps
        measured += seen[name]
        for k in bound:
            bound[k] += by[k]
    if measured <= 0.0:
        return None
    facts.setdefault("notes", {})["conv_roofline"] = (
        f"{bound['compute']} kernels compute-bound, "
        f"{bound['memory']} memory-bound")
    return 100.0 * ideal / measured
