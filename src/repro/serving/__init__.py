from .engine import EngineConfig, Request, ServingEngine
from .cluster import ServingCluster
from .kv_cache import BlockManager, OutOfBlocks
