"""The staged block store and a map task's writer into it."""

from sparkucx_tpu.store.hbm_store import HbmBlockStore
from sparkucx_tpu.store.writer import MapWriter

__all__ = ["HbmBlockStore", "MapWriter"]
