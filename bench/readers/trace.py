"""The reduced device trace (bench/xplane/reduce.py). spec["quantity"]:

  idle_share            1 - busy/window, the worst device
  module_ms_per_launch  device time over launches of the programs whose
                        name matches spec["match"] and, where
                        spec["contains_op"] is given, that run an operation
                        matching it (programs have no stable names yet: the
                        engine's decode and prefill are both `jit__lambda`
                        and are told apart by their activations' shape)
  op_time_share         device time of the operations whose name (own HLO
                        name and result shape, as `breakdown.device_ops`
                        prints them) matches spec["match"], over busy
                        time, both summed over the devices
  custom_call_share     time in custom calls (Pallas kernels) over busy
  collective_exposed_share   collective time with no other operation
                        running on that device, over the window, the worst
                        device
"""

import re


def read(sources, spec):
    tr = sources.get("trace")
    if not tr or not tr.get("devices"):
        return None
    devs = list(tr["devices"].values())
    q = spec["quantity"]
    if q == "idle_share":
        return 100.0 * max(d["idle_share"] for d in devs)
    if q == "custom_call_share":
        busy = sum(d["busy_s"] for d in devs)
        return 100.0 * sum(d["custom_call_s"] for d in devs) / busy if busy else None
    if q == "collective_exposed_share":
        return 100.0 * max(d["collective_exposed_s"] for d in devs) / tr["window_s"]
    if q == "op_time_share":
        pat = re.compile(spec["match"])
        busy = tr["busy_s"]  # a chip's mean, as the times in `op_s` are
        return 100.0 * sum(s for n, s in tr["op_s"].items()
                           if pat.search(n)) / busy if busy else None
    if q == "module_ms_per_launch":
        pat = re.compile(spec["match"])
        op = spec.get("contains_op")
        hit = [m for n, m in tr["modules"].items() if pat.search(n)
               and (op is None or any(re.search(op, o) for o in m["ops"]))]
        launches = sum(m["launches"] for m in hit)
        if not launches:
            return None
        return 1e3 * sum(m["total_s"] for m in hit) / launches
    raise ValueError(f"unknown trace quantity {q!r}")
