"""chipbench: the chip benchmark's harness. See chipbench/README.md."""
