"""The benchmark's own plain reference of the fabric and the tier."""
