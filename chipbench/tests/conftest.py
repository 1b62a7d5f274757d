"""Tests of the chip benchmark's harness; they run on the CPU
(``pytest chipbench/tests``) and never need a chip."""
