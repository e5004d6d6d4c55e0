"""Shared harness code: name resolution, traffic, statistics, traces."""
