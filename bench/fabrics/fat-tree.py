"""k-ary fat-tree (Al-Fares, Loukissas and Vahdat, SIGCOMM 2008).

k pods of k/2 edge and k/2 aggregation switches, (k/2)^2 core
switches, k/2 servers under every edge switch; 10 Gbps grey links on
one wavelength.  Switch powers and slot length are the paper's Table
II values for the electronic fabrics (arXiv:2008.03497 §IV-A): SG500
switches at 94.33 W, an SFP+ transceiver of 1 W per server, 1 s
slots, switch ingress limited to k x 10 Gbps.  Devices and links are
numbered in the order a pod-by-pod walk meets them.
"""
from reference import SERVER, SWITCH, FabricBuilder

LINK_GBPS = 10.0
SWITCH_W = 94.33
SERVER_W = 1.0


def build(k: int, slot_s: float = 1.0):
    b = FabricBuilder(n_wavelengths=1)
    half = k // 2
    core = [b.add(f"core{i}", SWITCH, SWITCH_W) for i in range(half * half)]
    switches = list(core)
    n_srv = 0
    for pod in range(k):
        aggs = [b.add(f"agg{pod}.{i}", SWITCH, SWITCH_W) for i in range(half)]
        edge = [b.add(f"edge{pod}.{i}", SWITCH, SWITCH_W)
                for i in range(half)]
        switches += aggs + edge
        for e in edge:
            for a in aggs:
                b.link(e, a, [LINK_GBPS])
            for _ in range(half):
                s = b.add(f"srv{pod}.{n_srv % (half * half)}", SERVER,
                          SERVER_W)
                n_srv += 1
                b.link(s, e, [LINK_GBPS])
        for i, a in enumerate(aggs):
            for j in range(half):
                b.link(a, core[i * half + j], [LINK_GBPS])
    for s in switches:
        b.sigma[s] = k * LINK_GBPS
    return b.build(slot_s=slot_s)
