"""The benchmark's own record of what it asked for and what it was granted,
and the comparison that decides ``correct``.

During the run the harness logs every registration with its weight, every
epoch's committed grants (with the allocator's rng state at the epoch's
start, the one input of an RRR epoch that is not cluster state) and every
completion.  After the window closes, :func:`check` replays that log against
the configuration's plain reference (``bench.spec.reference``):
each epoch's committed sequence must equal the reference's on the same
inputs, no committed grant may oversubscribe a machine or go to a framework
that did not ask, and the program's free resources at the end must equal
the replayed ones.
"""
from __future__ import annotations

import dataclasses

import numpy as np

from bench import reference


@dataclasses.dataclass
class Log:
    agents: list                       # [(name, capacity)], roster order
    events: list = dataclasses.field(default_factory=list)

    def place(self, fid, demand, wanted, phi, places):
        """A framework present before the window, with its placements."""
        self.events.append(("place", fid, tuple(demand), int(wanted),
                            float(phi), tuple(places)))

    def register(self, fid, demand, wanted, phi):
        self.events.append(("register", fid, tuple(demand), int(wanted),
                            float(phi)))

    def epoch(self, rng_state, grants, checked: bool):
        self.events.append(("epoch", rng_state, tuple(grants), checked))

    def complete(self, fid):
        self.events.append(("complete", fid))


@dataclasses.dataclass
class _Fw:
    demand: np.ndarray
    wanted: int
    phi: float
    held: dict                          # agent index -> executors


class Replay:
    """The cluster as the log says it is: free resources and frameworks."""

    def __init__(self, agents):
        self.names = [a for a, _ in agents]
        self.order = np.argsort(self.names, kind="stable")  # name order
        self.index = {a: j for j, a in enumerate(self.names)}
        self.cap = np.asarray([c for _, c in agents], np.float64)
        self.free = self.cap.copy()
        self.fws: dict = {}
        self.oversubscribed = 0

    def grant(self, fid, agent, n=1):
        fw, j = self.fws[fid], self.index[agent]
        self.free[j] -= n * fw.demand
        if (self.free[j] < -reference.EPS).any():
            self.oversubscribed += n
        fw.held[j] = fw.held.get(j, 0) + n

    def complete(self, fid):
        fw = self.fws.pop(fid)
        for j, n in fw.held.items():
            self.free[j] += n * fw.demand

    def inputs(self):
        """Rows (wanting frameworks in name order) and columns (machines in
        name order) of the next epoch: ``rows, D, tot, wanted, phi,
        free``."""
        rows = sorted(f for f, fw in self.fws.items()
                      if sum(fw.held.values()) < fw.wanted)
        D = np.asarray([self.fws[f].demand for f in rows]).reshape(
            len(rows), self.cap.shape[1])
        tot = np.asarray([sum(self.fws[f].held.values()) for f in rows],
                         np.float64)
        wanted = np.asarray([self.fws[f].wanted for f in rows], np.float64)
        phi = np.asarray([self.fws[f].phi for f in rows], np.float64)
        return rows, D, tot, wanted, phi, self.free[self.order]


#: the controls a configuration can name: each breaks one stated guarantee
#: of the reference it stands in for
CONTROLS = ("bfloat16_scores",)


def check(log: Log, config: dict, epoch, program_free: dict, *,
          control=None) -> dict:
    """Replay ``log`` against the reference ``epoch`` of ``config`` (a
    cell's ``reference``); the numbers compared, each ``{"value": v,
    "limit": 0}``.  ``program_free`` maps agent -> the program's free
    vector after the run.

    ``control`` puts a broken reference in the program's place and
    compares it with the true one: ``bfloat16_scores`` holds every score
    in bfloat16."""
    if control is not None and control not in CONTROLS:
        raise ValueError(f"unknown control {control!r}")
    score_round = (reference.bfloat16_round if control == "bfloat16_scores"
                   else None)
    rep = Replay(log.agents)
    ctot = rep.cap.sum(axis=0)
    compared = mismatched = unrequested = 0
    first_diff = None
    for ev in log.events:
        kind = ev[0]
        if kind == "place":
            _, fid, demand, wanted, phi, places = ev
            rep.fws[fid] = _Fw(np.asarray(demand, np.float64), wanted, phi,
                               {})
            for f, agent, n in places:
                rep.grant(f, agent, n)
        elif kind == "register":
            _, fid, demand, wanted, phi = ev
            rep.fws[fid] = _Fw(np.asarray(demand, np.float64), wanted, phi,
                               {})
        elif kind == "complete":
            rep.complete(ev[1])
        else:
            _, rng_state, grants, checked = ev
            if checked:
                rows, D, tot, wanted, phi, free = rep.inputs()
                rng = None
                if rng_state is not None:
                    rng = np.random.Generator(np.random.PCG64())
                    rng.bit_generator.state = rng_state
                seq = epoch(config, D=D, tot=tot, wanted=wanted, phi=phi,
                            free=free, ctot=ctot, rng=rng,
                            score_round=score_round)
                want = [(rows[n], rep.names[rep.order[j]]) for n, j in seq]
                compared += 1
                if want != list(grants):
                    mismatched += 1
                    if first_diff is None:
                        k = next((i for i, (a, b) in
                                  enumerate(zip(want, grants)) if a != b),
                                 min(len(want), len(grants)))
                        first_diff = (f"epoch {compared - 1}: grant {k} of "
                                      f"{len(grants)} committed / {len(want)}"
                                      f" reference")
            for fid, agent in grants:
                if fid in rep.fws:
                    rep.grant(fid, agent)
                else:
                    unrequested += 1
    free_diff = sum(
        1 for a, j in rep.index.items()
        if np.abs(np.asarray(program_free[a]) - rep.free[j]).max() > 1e-6)
    return {
        "epochs_compared": compared,
        "first_difference": first_diff,
        "checks": {
            "epochs_mismatched": {"value": mismatched, "limit": 0},
            "grants_oversubscribed": {"value": rep.oversubscribed,
                                      "limit": 0},
            "grants_unrequested": {"value": unrequested, "limit": 0},
            "machines_free_mismatched": {"value": free_diff, "limit": 0},
        },
    }
