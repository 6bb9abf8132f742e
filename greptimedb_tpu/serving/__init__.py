"""Concurrent serving layer: async query scheduler, per-tenant admission
and cross-query batched dispatch.

The front door every protocol server (servers/http.py, mysql.py,
postgres.py over servers/tcp.py) submits queries through (ROADMAP Open
item 1; Theseus, arXiv 2508.05029: at scale the win is scheduling
compute and data movement *across* queries, not inside one).  A
standalone instance always has one; its workers start with the first
submit.
"""

from greptimedb_tpu.serving.admission import TenantAdmission, TenantQuota
from greptimedb_tpu.serving.scheduler import QueryScheduler

__all__ = ["QueryScheduler", "TenantAdmission", "TenantQuota"]
