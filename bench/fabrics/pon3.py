"""The paper's AWGR PON cell, PON3 (arXiv:2008.03497 §III, Fig. 5a).

Four racks of four servers, each rack on a 4x4 polymer backplane
(12 W); tunable-laser servers (2 W) reach their rack's AWGR ingress
port on any wavelength but one per slot (eq. 47), and receive every
wavelength from the rack's AWGR egress port; an OLT card (217 W) sits
on the AWGR's fifth port pair.  The AWGR routes ingress s to egress d
on the wavelength of the paper's Table I (racks 1-4, then the OLT),
over 4 wavelengths.  Servers never relay (eq. 46); slots are 0.25 s;
ingress limits: OLT 4 x 10 Gbps, backplane servers x 10 Gbps.
"""
import numpy as np

from reference import PASSIVE, SERVER, SWITCH, FabricBuilder

LINK_GBPS = 10.0
OLT_W = 217.0
BACKPLANE_W = 12.0
TUNABLE_W = 2.0
# Table I: wavelength from rack/OLT port s (row) to port d (column)
TABLE_I = np.array([[-1, 2, 3, 0, 1],
                    [3, -1, 1, 2, 0],
                    [0, 3, -1, 1, 2],
                    [1, 0, 2, -1, 3],
                    [2, 1, 0, 3, -1]])


def build(n_racks: int = 4, servers_per_rack: int = 4, slot_s: float = 0.25):
    W = 4
    b = FabricBuilder(n_wavelengths=W)
    every = np.full(W, LINK_GBPS)
    grey = np.eye(W)[0] * LINK_GBPS
    olt = b.add("olt", SWITCH, OLT_W)
    b.sigma[olt] = 4 * LINK_GBPS
    ins, outs = [], []
    for r in range(n_racks):
        bp = b.add(f"backplane{r}", SWITCH, BACKPLANE_W)
        b.sigma[bp] = servers_per_rack * LINK_GBPS
        ins.append(b.add(f"awgr_in{r}", PASSIVE))
        outs.append(b.add(f"awgr_out{r}", PASSIVE))
        for i in range(servers_per_rack):
            s = b.add(f"srv{r}.{i}", SERVER, TUNABLE_W)
            b.link(s, bp, grey)
            b.arc(s, ins[-1], every)
            b.arc(outs[-1], s, every)
    ins.append(b.add("awgr_in_olt", PASSIVE))
    outs.append(b.add("awgr_out_olt", PASSIVE))
    b.arc(olt, ins[-1], every)
    b.arc(outs[-1], olt, every)
    for s in range(n_racks + 1):
        for d in range(n_racks + 1):
            if s != d:
                b.arc(ins[s], outs[d], np.eye(W)[TABLE_I[s, d]] * LINK_GBPS)
    return b.build(slot_s=slot_s, server_relay=False, one_wavelength_tx=True,
                   awgr_in=ins)
