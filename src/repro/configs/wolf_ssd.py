"""The paper's own system config (Table 2): the 16 GB simulated SSD.

Used by the SSD simulator benchmarks; ``scaled(f)`` shrinks the geometry
(ratios preserved) for CI-speed runs — equilibrium WA depends only on
LBA/PBA and B, which are kept."""
import dataclasses


@dataclasses.dataclass(frozen=True)
class SSDConfig:
    channels: int = 4
    luns_per_channel: int = 2
    blocks_per_lun: int = 1024
    pages_per_block: int = 128
    page_size: int = 16 * 1024
    lba_pba: float = 0.70

    @property
    def n_luns(self) -> int:
        return self.channels * self.luns_per_channel

    @property
    def n_blocks(self) -> int:
        return self.n_luns * self.blocks_per_lun

    @property
    def pba_pages(self) -> int:
        return self.n_blocks * self.pages_per_block

    @property
    def lba_pages(self) -> int:
        return int(self.pba_pages * self.lba_pba)

    def geometry(self):
        """This drive as the simulator's :class:`repro.core.ssd.Geometry`."""
        from repro.core.ssd import Geometry

        return Geometry(
            n_luns=self.n_luns, blocks_per_lun=self.blocks_per_lun,
            pages_per_block=self.pages_per_block, lba_pba=self.lba_pba,
        )

    def scaled(self, block_factor: int = 16, page_factor: int = 4) -> "SSDConfig":
        return dataclasses.replace(
            self,
            blocks_per_lun=max(4, self.blocks_per_lun // block_factor),
            pages_per_block=max(8, self.pages_per_block // page_factor),
        )


CONFIG = SSDConfig()
