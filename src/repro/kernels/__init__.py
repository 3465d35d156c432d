from repro.kernels.efta_attention import efta_attention_pallas
from repro.kernels.efta_paged import (PagedReport, efta_paged_attention_pallas,
                                      paged_fault_descriptor)
from repro.kernels.ops import attention, gather_block_kv
