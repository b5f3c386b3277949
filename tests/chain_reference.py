"""Serial-chain reference replay: the oracle for one-replica pools.

A direct task-by-task loop over the ``2n+1`` serial FIFO resources
(compute 0, link 0, compute 1, ...), written independently of
``repro.core.sim.simulate_stream``.  It covers what a serial chain
needs — unbatched service, Fig. 4 tx/rx offsets, hop-level exits and
static links — and nothing else: no pools, no batching, no trace sink,
no migration.  The replay over one unit-speed replica per tier must
equal it bit for bit.
"""

from typing import List, NamedTuple, Sequence, Tuple

Interval = Tuple[float, float]


class ChainReplay(NamedTuple):
    done: List[float]
    compute_busy: Tuple[float, ...]
    link_busy: Tuple[float, ...]
    compute_intervals: Tuple[Tuple[Interval, ...], ...]
    link_intervals: Tuple[Tuple[Interval, ...], ...]


def serial_chain(plans: Sequence, arrivals: Sequence[float]) -> ChainReplay:
    n_hops = len(plans[0].tx)
    comp_free, link_free = [0.0] * (n_hops + 1), [0.0] * n_hops
    comp_busy, link_busy = [0.0] * (n_hops + 1), [0.0] * n_hops
    comp_iv = [[] for _ in range(n_hops + 1)]
    link_iv = [[] for _ in range(n_hops)]
    done = []
    for p, arr in zip(plans, arrivals):
        last = n_hops if p.exit_hop is None else p.exit_hop
        start = max(arr, comp_free[0])
        fin = start + p.compute[0]
        comp_free[0] = fin
        comp_busy[0] += p.compute[0]
        comp_iv[0].append((start, fin))
        for k in range(last):
            off = p.tx_offset[k]
            tx_ready = fin if off is None or off >= p.compute[k] \
                else start + off
            t0 = max(tx_ready, link_free[k])
            t1 = t0 + p.tx[k]
            link_free[k] = t1
            link_busy[k] += p.tx[k]
            link_iv[k].append((t0, t1))
            roff = p.rx_offset[k]
            c_ready = t1 if roff is None else max(t0 + roff, tx_ready)
            start = max(c_ready, comp_free[k + 1])
            comp_busy[k + 1] += p.compute[k + 1]
            comp_iv[k + 1].append((start, start + p.compute[k + 1]))
            fin = max(start + p.compute[k + 1], t1)   # all data landed
            comp_free[k + 1] = fin
        done.append(fin)
    return ChainReplay(done, tuple(comp_busy), tuple(link_busy),
                       tuple(tuple(iv) for iv in comp_iv),
                       tuple(tuple(iv) for iv in link_iv))
