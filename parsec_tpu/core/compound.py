"""Compound taskpools: sequential composition.

Reference: parsec_compose (runtime.h:518) / compound.c (134 LoC) — a
compound taskpool runs its members one after another; member N+1 is
enqueued when member N terminates.
"""

from __future__ import annotations

from typing import List

from .taskpool import Taskpool


class CompoundTaskpool(Taskpool):
    def __init__(self, members: List[Taskpool]):
        super().__init__(name="compound(" + "+".join(m.name for m in members) + ")")
        self.members = list(members)
        self._next = 0
        # handed its pool: not a method bound to the pool it is stored on
        self.startup_hook = CompoundTaskpool._compound_startup

    def _compound_startup(self) -> List:
        # one synthetic task: "run all members in sequence"
        self.set_nb_tasks(1)
        self._start_next()
        return []

    def _start_next(self) -> None:
        if self._next >= len(self.members):
            # all members done → compound done (monitor has 1 synthetic task)
            self.addto_nb_tasks(-1)
            return
        member = self.members[self._next]
        self._next += 1
        prev_cb = member.on_complete

        def _chain(tp, _prev=prev_cb):
            # the member is its owner's again: a finished compound and
            # its members hold each other no longer
            tp.on_complete = _prev
            if _prev is not None:
                _prev(tp)
            if tp.error is not None:
                # aborted member: don't run later stages on failed data —
                # propagate the abort to the compound (parsec_abort analog)
                self.abort(tp.error)
                return
            self._start_next()

        member.on_complete = _chain
        self.context.add_taskpool(member)


def compose(a: Taskpool, b: Taskpool) -> CompoundTaskpool:
    """parsec_compose analog: run ``a`` then ``b``. Composes iteratively:
    compose(compose(a, b), c) flattens into one compound."""
    if isinstance(a, CompoundTaskpool) and a.context is None:
        a.members.append(b)
        a.name = "compound(" + "+".join(m.name for m in a.members) + ")"
        return a
    return CompoundTaskpool([a, b])
