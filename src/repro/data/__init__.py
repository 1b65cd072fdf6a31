from repro.data.synthetic import (DATASETS, DatasetSpec, make_dataset,
                                  make_id_universe)
from repro.data.vertical import VerticalPartition, partition_features

__all__ = [
    "DATASETS", "DatasetSpec", "make_dataset", "make_id_universe",
    "VerticalPartition", "partition_features",
]
