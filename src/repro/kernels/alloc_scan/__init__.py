from repro.kernels.alloc_scan.kernel import alloc_scan_pallas, member_block

__all__ = ["alloc_scan_pallas", "member_block"]
