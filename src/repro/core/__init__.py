"""The paper's primary contribution: PDGraph demand modeling, Gittins-policy
queue management, and PDGraph-driven backend prewarming (Hermes)."""
from repro.core.pdgraph import PDGraph, UnitNode, BackendSpec  # noqa: F401
from repro.core.gittins import gittins_rank_hist, gittins_rank_samples  # noqa: F401
from repro.core.arena import QueueState  # noqa: F401
from repro.core.refresh_config import RefreshConfig  # noqa: F401
