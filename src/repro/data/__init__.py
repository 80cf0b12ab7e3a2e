from .pipeline import (PrefetchLoader, RowBlocks,  # noqa: F401
                       synthetic_tabular, synthetic_tabular_stream)
