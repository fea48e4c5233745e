"""The paper's contribution: adaptive early-exit A-kNN for dense retrieval."""
from repro.core.ivf import (DeltaView, IVFIndex, SearchResult,
                            abstract_index, brute_force, build_index,
                            exact_topk, extract_features, min_probes_labels,
                            probe_trace, search, validate_alignment)
from repro.core import metrics, policies
