"""Auxiliary subsystems: process/TPU gauges, checkpointing, the journal
and the compilation cache."""
