"""Production serving tier: paged KV-cache manager + continuous batching.

See DESIGN.md §8.  ``Engine`` is the scheduler loop; ``KVCacheManager`` owns
slots/pages/positions (``PagedKVCacheManager`` makes pages real: free-list
:class:`PageAllocator` + per-slot block tables, non-contiguous layout);
``repro.control.AdmissionController`` co-schedules admission with the rail
plan, priced off actual free pages.
"""
from repro.serve.cache import (HostPagePool, KVCacheManager, PageAllocator,
                               PagedKVCacheManager)
from repro.serve.engine import Engine, Request
from repro.serve.scheduler import SlotWork, TickPlan, compose
from repro.serve.step import sample

__all__ = ["Engine", "Request", "KVCacheManager", "PagedKVCacheManager",
           "PageAllocator", "HostPagePool",
           "SlotWork", "TickPlan", "compose", "sample"]
