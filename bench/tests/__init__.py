"""Tests of the chip benchmark that run on the CPU."""
