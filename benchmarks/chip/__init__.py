"""The chip benchmark: cells, traffic, reference and trace reduction."""
