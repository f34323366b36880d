from repro.configs.base import (ARCH_IDS, SHAPES, IndexerConfig, MLAConfig,
                                ModelConfig, MoEConfig, ShapeCell, SSMConfig,
                                cells_for, get_config, reduced)
