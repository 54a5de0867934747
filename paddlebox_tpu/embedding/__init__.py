from paddlebox_tpu.embedding.config import EmbeddingConfig  # noqa: F401
from paddlebox_tpu.embedding.store import HostEmbeddingStore  # noqa: F401
from paddlebox_tpu.embedding.store import ShardedEmbeddingStore  # noqa: F401
from paddlebox_tpu.embedding.spill_store import SpillEmbeddingStore  # noqa: F401
from paddlebox_tpu.embedding.working_set import PassWorkingSet  # noqa: F401
from paddlebox_tpu.embedding.replica_cache import (ReplicaCache,  # noqa: F401
                                                   InputTable,
                                                   pull_cache_value)
from paddlebox_tpu.embedding import gating  # noqa: F401
from paddlebox_tpu.embedding import quant  # noqa: F401
from paddlebox_tpu.embedding import tiering  # noqa: F401
from paddlebox_tpu.embedding import sharded  # noqa: F401
from paddlebox_tpu.embedding import exchange  # noqa: F401
