"""repro — LISA (Low-Cost Inter-Linked Subarrays) as a JAX/TPU framework.

Faithful DRAM-substrate reproduction + the paper's connectivity insight as a
first-class distributed-runtime feature.  See DESIGN.md.
"""

__version__ = "1.0.0"
