"""Shared fixtures.  NOTE: no XLA_FLAGS here — tests must see the single
real CPU device; only launch/dryrun.py forces 512 placeholder devices."""

import contextlib

import numpy as np
import pytest

from repro.core import (ClusterState, QSCH, QSCHConfig, QueuePolicy,
                        QuotaManager, QuotaMode, RSCH, RSCHConfig,
                        small_topology)


@pytest.fixture
def topo():
    return small_topology(n_nodes=16, gpus_per_node=8, nodes_per_leaf=4)


@pytest.fixture
def state(topo):
    return ClusterState.create(topo)


def make_qsch(topo, state, *, policy=QueuePolicy.BACKFILL,
              quota=None, mode=QuotaMode.ISOLATED,
              incremental=True, rsch_config=None, **cfg_kw):
    qm = QuotaManager(quota or {"t0": {0: 1024}}, mode=mode)
    rsch = RSCH(topo, rsch_config or RSCHConfig())
    cfg = QSCHConfig(policy=policy, **cfg_kw)
    return QSCH(qm, rsch, cfg, incremental_snapshots=incremental)


class PhaseLog:
    """A recording observer for RSCH's and the score call's
    ``obs_phase`` / ``obs_count`` sites: the order of phase entries and
    exits, the uid a phase was given, and counter totals.
    ``counts=False`` leaves out ``count``, as an observer without
    counters has it."""

    audit_on = False
    phase_uid = True

    def __init__(self, counts: bool = True):
        self.events = []
        self.uids = {}
        self.counted = {}
        if not counts:
            self.count = None

    def phase(self, name, uid=None):
        @contextlib.contextmanager
        def span():
            self.events.append(("enter", name))
            yield
            self.events.append(("exit", name))

        if uid is not None:
            self.uids[name] = uid
        return span()

    def count(self, name, n):
        self.counted[name] = self.counted.get(name, 0) + n

    def interval(self, name):
        """Event indices of the first entry and exit of ``name``."""
        return (self.events.index(("enter", name)),
                self.events.index(("exit", name)))
