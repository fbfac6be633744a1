"""BCube_k(n) (Guo, Lu, Li, Wu, Zhang, Shi, Tian, Zhang and Lu, SIGCOMM
2009, §3.1).

n^(k+1) servers, each with k+1 ports; k+1 levels of n^k switches with
n ports each.  A server's address is its digits a_k .. a_0 (a_k most
significant); port i of the level-l switch with address s_(k-1) .. s_0
connects to the level-l port of server s_(k-1) .. s_l i s_(l-1) .. s_0,
so a level-l switch joins the servers whose addresses differ only in
digit l.  10 Gbps grey links on
one wavelength.  Servers are numbered in address order, then the
switches level by level, each level's in address order; a server's
links go up level by level.

Powers and slot length are the paper's values for the server-centric
electronic fabrics (arXiv:2008.03497 §IV-A, Table II): a server holds
PE10G2T-SR two-port NICs, 14 W each, enough for its k+1 ports, and
pays 14.29 W per Gbps it offloads; SG500 switches at 94.33 W with
ingress limited to n x 10 Gbps; 1 s slots.  Servers relay.
"""
import itertools

from reference import SERVER, SWITCH, FabricBuilder

LINK_GBPS = 10.0
SWITCH_W = 94.33
NIC_W = 14.0            # one PE10G2T-SR: two 10G ports
NIC_PORTS = 2
NIC_W_PER_GBPS = 14.29


def build(n: int, k: int, slot_s: float = 1.0):
    b = FabricBuilder(n_wavelengths=1)
    n_nics = (k + 1 + NIC_PORTS - 1) // NIC_PORTS
    servers = {}
    for a in itertools.product(range(n), repeat=k + 1):
        servers[a] = b.add("srv" + ".".join(map(str, a)), SERVER,
                           n_nics * NIC_W, NIC_W_PER_GBPS)
    switches = {}
    for level in range(k + 1):
        for rest in itertools.product(range(n), repeat=k):
            sw = b.add(f"sw{level}" + "".join(f".{d}" for d in rest),
                       SWITCH, SWITCH_W)
            switches[level, rest] = sw
            b.sigma[sw] = n * LINK_GBPS
    for a, s in servers.items():
        for level in range(k + 1):
            digit = k - level           # a[digit] is a_level
            b.link(s, switches[level, a[:digit] + a[digit + 1:]],
                   [LINK_GBPS])
    return b.build(slot_s=slot_s)
