"""Traffic files (``<name>.json``) and the one generator that reads them."""
