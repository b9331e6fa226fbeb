"""repro — PD-SGDM / CPD-SGDM decentralized training on JAX."""
